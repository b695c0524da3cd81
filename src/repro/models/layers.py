"""Shared model substrate: parameter specs, norms, rotary embeddings,
memory-efficient attention (chunked online-softmax), GQA, SWA, MLP.

All attention paths avoid materializing the full [S, S] score matrix — the
double-scan chunked implementation is the portable oracle; the Pallas
flash-attention kernel (kernels/flash_attention.py) is the TPU fast path.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.sharding.rules import constrain

DEFAULT_DTYPE = jnp.bfloat16


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------
class ParamSpec(NamedTuple):
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]     # logical axes, len == rank
    init: str = "normal"                # normal | zeros | ones | embed
    dtype: Any = DEFAULT_DTYPE
    scale: float = 1.0                  # fan-in style scale multiplier


def _init_one(key, spec: ParamSpec):
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    fan_in = spec.shape[0] if len(spec.shape) > 1 else max(spec.shape[-1], 1)
    if spec.init == "embed":
        std = 0.02
    else:
        std = spec.scale / math.sqrt(fan_in)
    return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(spec.dtype)


def init_params(key, specs) -> Any:
    """Materialize a pytree of ParamSpec into arrays (deterministic per path)."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [_init_one(k, s) for k, s in zip(keys, leaves)])


def abstract_params(specs) -> Any:
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), specs,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def param_axes(specs) -> Any:
    return jax.tree.map(lambda s: s.axes, specs,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def stacked(spec: ParamSpec, n: int, axis_name: str = "layers") -> ParamSpec:
    """Stack a per-block spec n times along a leading scan dimension."""
    return ParamSpec((n,) + spec.shape, (axis_name,) + spec.axes,
                     spec.init, spec.dtype, spec.scale)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def rmsnorm(x, scale, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def layernorm(x, scale, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(x.dtype) * scale + bias


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: [..., S, H, D]; positions: broadcastable to [..., S]."""
    d = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(d, theta))                      # [D/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs   # [..., S, D/2]
    angles = angles[..., :, None, :]                               # [..., S, 1, D/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, theta: float, sections=(16, 24, 24)):
    """Multimodal RoPE (Qwen2-VL): positions3 [..., S, 3] = (t, h, w) ids.

    The head_dim/2 frequency slots are split into 3 sections, each rotated by
    its own position stream.
    """
    d = x.shape[-1]
    half = d // 2
    sec = np.asarray(sections, dtype=np.int64)
    sec = (sec * half // sec.sum()).tolist()
    sec[-1] = half - sum(sec[:-1])
    freqs = jnp.asarray(rope_freqs(d, theta))                      # [half]
    parts = []
    start = 0
    for i, width in enumerate(sec):
        f = freqs[start:start + width]
        ang = positions3[..., :, i][..., :, None].astype(jnp.float32) * f
        parts.append(ang)
        start += width
    angles = jnp.concatenate(parts, axis=-1)[..., :, None, :]      # [..., S, 1, half]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention — chunked online-softmax (training/prefill), O(S * chunk) memory
# ---------------------------------------------------------------------------
NEG_INF = -1e30


def pick_chunk(seq: int, target: int) -> int:
    """Largest power-of-two-ish chunk <= target that divides seq."""
    c = min(seq, target)
    while seq % c:
        c //= 2
    return max(c, 1)


ATTN_Q_CHUNK = 2048      # tile knobs: smaller tiles cut transient VMEM/HBM
ATTN_KV_CHUNK = 2048     # pressure at some redundancy cost (perf knob)


def chunked_attention(q, k, v, *, causal: bool, window: int = 0,
                      q_offset: int = 0, q_chunk: int = 0, kv_chunk: int = 0):
    """Flash-style attention via double lax.scan.

    q: [B, Sq, H, D]; k, v: [B, Skv, KVH, D]. GQA via head broadcasting.
    window > 0 limits attention to the trailing ``window`` keys (SWA).
    Returns [B, Sq, H, D].
    """
    B, Sq, H, D = q.shape
    Skv, KVH = k.shape[1], k.shape[2]
    rep = H // KVH
    qc = pick_chunk(Sq, q_chunk or ATTN_Q_CHUNK)
    kc = pick_chunk(Skv, kv_chunk or ATTN_KV_CHUNK)
    nq, nk = Sq // qc, Skv // kc
    scale = 1.0 / math.sqrt(D)

    # [B, S, H, D] -> [nq, B, qc, KVH, rep, D]
    qs = q.reshape(B, nq, qc, KVH, rep, D).transpose(1, 0, 2, 3, 4, 5)
    ks = k.reshape(B, nk, kc, KVH, D).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nk, kc, KVH, D).transpose(1, 0, 2, 3, 4)

    q_pos_base = jnp.arange(qc)
    k_pos_base = jnp.arange(kc)

    def q_step(_, qi_blk):
        qi, qblk = qi_blk
        q_pos = q_offset + qi * qc + q_pos_base                    # [qc]
        qblk = qblk.astype(jnp.float32)

        def kv_step(carry, ki_blk):
            m, l, acc = carry
            ki, kblk, vblk = ki_blk
            k_pos = ki * kc + k_pos_base                            # [kc]
            # scores: [B, KVH, rep, qc, kc] in f32
            s = jnp.einsum("bqgrd,bkgd->bgrqk", qblk,
                           kblk.astype(jnp.float32),
                           preferred_element_type=jnp.float32) * scale
            mask = jnp.ones((qc, kc), bool)
            if causal:
                mask &= q_pos[:, None] >= k_pos[None, :]
            if window:
                mask &= k_pos[None, :] > q_pos[:, None] - window
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))                       # [B,KVH,rep,qc]
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            pv = jnp.einsum("bgrqk,bkgd->bgrqd", p, vblk.astype(jnp.float32),
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KVH, rep, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KVH, rep, qc), jnp.float32)
        a0 = jnp.zeros((B, KVH, rep, qc, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nk), ks, vs))
        out = acc / jnp.maximum(l, 1e-30)[..., None]                # [B,KVH,rep,qc,D]
        out = out.transpose(0, 3, 1, 2, 4).reshape(B, qc, H, D)
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_step, None, (jnp.arange(nq), qs))      # [nq, B, qc, H, D]
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, D)


def decode_attention(q, k_cache, v_cache, cache_len, *, rolling: bool = False,
                     start=None):
    """Single-token attention against a (possibly seq-sharded) KV cache.

    q: [B, 1, H, D]; caches: [B, KVH, S, D] (head-major layout so the rules
    engine shards heads over ``model`` when divisible, else sequence).
    cache_len: int32 scalar — number of valid entries. With ``rolling=True``
    (sliding-window buffers) every slot < min(cache_len, S) is valid.
    start: optional per-batch [B] (or [1]) int32 — the first valid absolute
    position per batch row. Used by the serving engine's continuous batching:
    requests share one position timeline, so a slot admitted late masks out
    whatever its cache holds before its own prompt.
    """
    B, _, H, D = q.shape
    KVH, S = k_cache.shape[1], k_cache.shape[2]
    rep = H // KVH
    scale = 1.0 / math.sqrt(D)
    qf = q.reshape(B, KVH, rep, D).astype(jnp.float32)
    s = jnp.einsum("bgrd,bgsd->bgrs", qf, k_cache.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale       # [B,KVH,rep,S]
    pos = jnp.arange(S)
    limit = jnp.minimum(cache_len, S) if rolling else cache_len
    valid = pos < limit
    if start is not None:
        if rolling:
            # slot p last written at absolute position n-1 - ((n-1-p) mod S)
            abs_pos = cache_len - 1 - ((cache_len - 1 - pos) % S)
        else:
            abs_pos = pos
        valid = valid[None, :] & (abs_pos >= jnp.reshape(start, (-1, 1)))
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    else:
        s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrs,bgsd->bgrd", p, v_cache.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, D).astype(q.dtype)


def paged_decode_attention(q, k_pages, v_pages, block_tables, seq_lens, *,
                           use_kernel: bool = False):
    """Single-token attention over a block-table-indexed paged KV cache.

    q: [B, 1, H, D]; k_pages, v_pages: [num_pages, KVH, page_size, D] shared
    pools; block_tables: [B, max_pages] int32 (page ids in sequence order,
    unused tail entries -> reserved null page 0); seq_lens: [B] int32 valid
    tokens per row, *including* the token just written for this step.

    Unlike ``decode_attention``'s shared-timeline cache, each row's work is
    bounded by its own capacity (max_pages * page_size) instead of the
    engine-lifetime horizon, and positions are 0-based per request — no
    ``start`` masking, no RoPE offset bookkeeping. use_kernel routes to
    the Pallas kernel (kernels/paged_attention.py), which copies only each
    row's live pages; the paged local backend binds it on a TPU. Otherwise
    the jnp path gathers every table entry (the kernels/ref.py oracle).
    """
    from repro.kernels import ops as KO
    B, _, H, D = q.shape
    with jax.named_scope("paged_attention"):
        out = KO.paged_attention(q.reshape(B, H, D), k_pages, v_pages,
                                 block_tables, seq_lens,
                                 use_kernel=use_kernel)
        return out.reshape(B, 1, H, D).astype(q.dtype)


def paged_write(k_pages, v_pages, k_new, v_new, block_tables, seq_lens):
    """Scatter one new token per row into the page pools.

    k_new, v_new: [B, KVH, D] — token at position ``seq_lens[b]`` of row b,
    which lives in page ``block_tables[b, seq_lens[b] // Pg]`` at offset
    ``seq_lens[b] % Pg``. Rows whose table entry is the null page (idle
    slots, exhausted tables — gather clamps out-of-range) are *dropped*:
    the write index is pushed out of range and scatter-mode ``drop``
    discards it, so page 0 is immutable for the pool's whole lifetime (a
    PagePool invariant the property tests audit). Ref-counted sharing
    (copy-on-write prefixes) relies on the same honor system one level up:
    the engine forks any page whose refcount exceeds 1 before it can be
    named here as a write target, so this scatter only ever lands on pages
    with exactly one owner."""
    N, _, Pg, _ = k_pages.shape
    with jax.named_scope("kv_write"):
        page = jnp.take_along_axis(
            block_tables, (seq_lens // Pg)[:, None], axis=1)[:, 0]  # [B]
        page = jnp.where(page == 0, N, page)  # null target -> out of range
        off = seq_lens % Pg
        # advanced indices split by the head slice put the batch dim first
        k_pages = k_pages.at[page, :, off].set(k_new, mode="drop")
        v_pages = v_pages.at[page, :, off].set(v_new, mode="drop")
        return k_pages, v_pages


def chunk_prefill_attention(q, k, v, k_pages, v_pages, block_tables,
                            prior_len):
    """Attention for one prefill *chunk* resuming at offset ``prior_len``.

    q: [B, C, H, D] chunk queries (absolute positions prior_len + i);
    k, v: [B, C, KVH, D] the chunk's own fresh keys/values;
    k_pages, v_pages: [N, KVH, Pg, D] shared pools already holding this
    row's positions < prior_len; block_tables: [B, MP] the row's pages in
    sequence order (null-page-0 tails). prior_len: traced int32 scalar.

    Each query attends (a) every pool position < prior_len gathered through
    the block table — entries past the written prefix (the chunk's own
    freshly-acquired pages, null tails, or the not-yet-valid remainder of a
    COW-adopted page) are masked, and (b) the chunk's own keys causally.
    Keys come from the *fresh* k/v, not the pool, so the caller scatters
    the chunk's KV after attention (drop-sentinel pattern — shared pages
    are never written). Chunk and page sizes need not divide each other.
    Returns [B, C, H, D].
    """
    B, C, H, D = q.shape
    KVH = k.shape[2]
    rep = H // KVH
    _, _, Pg, _ = k_pages.shape
    MP = block_tables.shape[1]
    scale = 1.0 / math.sqrt(D)
    # prior context: gather the row's pages -> [B, MP*Pg, KVH, D]
    kp = k_pages[block_tables]                      # [B, MP, KVH, Pg, D]
    kp = kp.transpose(0, 1, 3, 2, 4).reshape(B, MP * Pg, KVH, D)
    vp = v_pages[block_tables]
    vp = vp.transpose(0, 1, 3, 2, 4).reshape(B, MP * Pg, KVH, D)
    qf = q.reshape(B, C, KVH, rep, D).astype(jnp.float32)
    s_prior = jnp.einsum("bqgrd,bkgd->bgrqk", qf, kp.astype(jnp.float32),
                         preferred_element_type=jnp.float32) * scale
    k_pos = jnp.arange(MP * Pg)
    s_prior = jnp.where((k_pos < prior_len)[None, None, None, None, :],
                        s_prior, NEG_INF)
    # the chunk's own keys, causal within the chunk
    s_self = jnp.einsum("bqgrd,bkgd->bgrqk", qf, k.astype(jnp.float32),
                        preferred_element_type=jnp.float32) * scale
    causal = jnp.arange(C)[:, None] >= jnp.arange(C)[None, :]
    s_self = jnp.where(causal[None, None, None], s_self, NEG_INF)
    s = jnp.concatenate([s_prior, s_self], axis=-1)
    p = jax.nn.softmax(s, axis=-1)
    vcat = jnp.concatenate([vp, v], axis=1).astype(jnp.float32)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", p, vcat,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, C, H, D).astype(q.dtype)


def paged_write_chunk(k_pages, v_pages, k_new, v_new, pages, offs):
    """Scatter one chunk's KV into the pools: position i of the chunk lands
    in ``(pages[i], offs[i])``. Right padding, COW-shared pages, and any
    other must-not-write position carry the out-of-range sentinel
    (num_pages) in ``pages`` and are dropped — the same immutability
    contract as ``paged_write`` (page 0 and shared pages are never
    touched). k_new, v_new: [C, KVH, D]; pages, offs: [C] int32."""
    with jax.named_scope("kv_write"):
        k_pages = k_pages.at[pages, :, offs].set(k_new, mode="drop")
        v_pages = v_pages.at[pages, :, offs].set(v_new, mode="drop")
        return k_pages, v_pages


def roll_into_window(kv_hd, total_len: int, window: int):
    """Scatter the last W=min(window, total_len) tokens of [B, KVH, W, D]
    into a [B, KVH, window, D] rolling buffer at slot (absolute index %%
    window) — so a decode step at position ``len`` (writing slot ``len %%
    window``) evicts exactly the oldest cached token."""
    B, KVH, W, D = kv_hd.shape
    abs_idx = np.arange(total_len - W, total_len)
    slots = abs_idx % window
    buf = jnp.zeros((B, KVH, window, D), kv_hd.dtype)
    return buf.at[:, :, slots].set(kv_hd)


# ---------------------------------------------------------------------------
# Projections / MLP
# ---------------------------------------------------------------------------
def linear(x, w, b=None):
    y = jnp.einsum("...d,df->...f", x, w)
    if b is not None:
        y = y + b
    return y


def swiglu(x, wi, wg, wo, bi=None, bg=None, bo=None):
    h = jax.nn.silu(linear(x, wg, bg)) * linear(x, wi, bi)
    h = constrain(h, ("act_batch", "act_seq", "act_mlp"))
    return linear(h, wo, bo)


def gelu_mlp(x, wi, wo, bi=None, bo=None):
    h = jax.nn.gelu(linear(x, wi, bi))
    h = constrain(h, ("act_batch", "act_seq", "act_mlp"))
    return linear(h, wo, bo)


# ---------------------------------------------------------------------------
# Attention module (projection + rope + attend), shared by all families
# ---------------------------------------------------------------------------
def attn_specs(cfg, prefix_bias: bool = False) -> Dict[str, ParamSpec]:
    d, H, KVH, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    use_bias = cfg.use_bias or prefix_bias
    s = {
        "wq": ParamSpec((d, H, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, KVH, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, KVH, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((H, hd, d), ("heads", "head_dim", "embed")),
    }
    if use_bias:
        s.update({
            "bq": ParamSpec((H, hd), ("heads", "head_dim"), "zeros"),
            "bk": ParamSpec((KVH, hd), ("kv_heads", "head_dim"), "zeros"),
            "bv": ParamSpec((KVH, hd), ("kv_heads", "head_dim"), "zeros"),
        })
    return s


def attn_qkv(p, x, positions, cfg, pos3=None):
    """Project to q, k, v and apply positional rotation. x: [B, S, D]."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dgk->bsgk", x, p["wk"])
    v = jnp.einsum("bsd,dgk->bsgk", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = constrain(q, ("act_batch", "act_seq", "act_heads", None))
    k = constrain(k, ("act_batch", "act_seq", "act_kv_heads", None))
    if cfg.pos_type == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    elif cfg.pos_type == "mrope":
        assert pos3 is not None, "mrope needs 3-component positions"
        q = apply_mrope(q, pos3, cfg.rope_theta)
        k = apply_mrope(k, pos3, cfg.rope_theta)
    return q, k, v


def attn_out(p, ctx):
    return jnp.einsum("bshk,hkd->bsd", ctx, p["wo"])


# ---------------------------------------------------------------------------
# Cross-entropy over a sharded vocab, chunked over sequence (never
# materializes [B, S, V] logits).
# ---------------------------------------------------------------------------
def chunked_softmax_xent(h, w_head, labels, *, chunk: int = 512,
                         label_mask=None):
    """h: [B, S, D]; w_head: [D, V]; labels: [B, S] int32. Returns mean nll."""
    B, S, D = h.shape
    c = min(chunk, S)
    while S % c:
        c //= 2
    n = S // c
    hs = h.reshape(B, n, c, D).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, n, c).transpose(1, 0, 2)
    if label_mask is None:
        ms = jnp.ones((n, B, c), jnp.float32)
    else:
        ms = label_mask.reshape(B, n, c).transpose(1, 0, 2).astype(jnp.float32)

    def step(carry, blk):
        tot, cnt = carry
        hb, lb, mb = blk
        logits = jnp.einsum("bcd,dv->bcv", hb, w_head,
                            preferred_element_type=jnp.float32)
        logits = constrain(logits, ("act_batch", "act_seq", "act_vocab"))
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lb[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * mb
        return (tot + nll.sum(), cnt + mb.sum()), None

    # remat: never keep the f32 logits chunks alive for the backward pass
    (tot, cnt), _ = jax.lax.scan(jax.checkpoint(step),
                                 (jnp.float32(0), jnp.float32(0)),
                                 (hs, ls, ms))
    return tot / jnp.maximum(cnt, 1.0)
