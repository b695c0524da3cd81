"""Operations and bytes that the algorithm needs, per call, from shapes.

Only useful work counts: a matmul is 2 FLOPs per multiply-add of the real
tokens, attention is counted at each token's real context (causal), the head
is counted only for the rows whose logits are used, and bucket padding,
pages past a row's length and recomputed work do not count. So a share of a
peak built from these counts cannot pass 100% unless the time leaves out
part of the work.

``m`` is a model dict with the keys ``num_layers``, ``d_model``,
``num_heads``, ``num_kv_heads``, ``head_dim``, ``d_ff``, ``vocab_size``.
"""
from __future__ import annotations

from typing import Dict, Iterable

BF16 = 2


def block_matmul_params(m: Dict) -> int:
    """Matmul weights of one block: q, k, v, o projections and the gated
    MLP (up, gate, down)."""
    d, H, K, hd, ff = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                       m["head_dim"], m["d_ff"])
    return d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * ff


def head_params(m: Dict) -> int:
    return m["d_model"] * m["vocab_size"]


def weight_bytes(m: Dict, dtype_bytes: int = BF16) -> int:
    """Bytes one forward call reads from the weights: every block's
    matmuls and norms, the final norm and the head (the embedding rows
    gathered are a few kilobytes and are left out)."""
    L, d = m["num_layers"], m["d_model"]
    return dtype_bytes * (L * (block_matmul_params(m) + 2 * d) + d
                          + head_params(m))


def kv_bytes_per_token(m: Dict, dtype_bytes: int = BF16) -> int:
    """Keys and values of one token over every layer."""
    return (2 * m["num_layers"] * m["num_kv_heads"] * m["head_dim"]
            * dtype_bytes)


def attention_flops(m: Dict, pairs: int) -> int:
    """QK^T and PV for ``pairs`` (query, key) pairs over every layer."""
    return 4 * m["num_layers"] * m["num_heads"] * m["head_dim"] * pairs


def causal_pairs(offset: int, n: int) -> int:
    """(query, key) pairs of ``n`` tokens at positions offset .. offset+n-1,
    each attending to itself and every earlier position."""
    return n * offset + n * (n + 1) // 2


def prefill_flops(m: Dict, offset: int, n: int, head_rows: int = 1) -> int:
    """One prefill call of ``n`` real tokens after ``offset`` cached ones,
    whose logits are read at ``head_rows`` positions."""
    return (2 * n * m["num_layers"] * block_matmul_params(m)
            + attention_flops(m, causal_pairs(offset, n))
            + 2 * head_rows * head_params(m))


def prefill_bytes(m: Dict, offset: int, n: int) -> int:
    """Weights once, the ``offset`` cached tokens' KV read, ``n`` tokens'
    KV written."""
    kv = kv_bytes_per_token(m)
    return weight_bytes(m) + kv * (offset + n)


def decode_flops(m: Dict, contexts: Iterable[int]) -> int:
    """One decode step: one token per row, row b attending ``contexts[b]``
    positions (its own new token included)."""
    ctx = list(contexts)
    return (2 * len(ctx) * (m["num_layers"] * block_matmul_params(m)
                            + head_params(m))
            + attention_flops(m, sum(ctx)))


def decode_bytes(m: Dict, contexts: Iterable[int]) -> int:
    """Weights once, each row's cached KV read and its new token's KV
    written."""
    return weight_bytes(m) + kv_bytes_per_token(m) * sum(contexts)


def paged_attention_bytes(m: Dict, contexts: Iterable[int]) -> int:
    """The least bytes paged decode attention moves: each row's live KV
    read once, plus the queries and outputs (bf16)."""
    ctx = list(contexts)
    qo = 2 * len(ctx) * m["num_layers"] * m["num_heads"] * m["head_dim"] * BF16
    return kv_bytes_per_token(m) * sum(ctx) + qo


def seal_bytes(m: Dict, pages: int, page_size: int) -> int:
    """Sealing (or unsealing) ``pages`` KV pages: the plaintext read and
    the ciphertext of the same size written."""
    return 2 * pages * page_size * kv_bytes_per_token(m)
