"""FLOP and byte counts against hand counts at a small shape, and the
table of peaks."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import pytest  # noqa: E402

import costs  # noqa: E402
from peaks import PEAKS, peaks_for  # noqa: E402

M = {"num_layers": 2, "d_model": 8, "num_heads": 2, "num_kv_heads": 1,
     "head_dim": 4, "d_ff": 16, "vocab_size": 32}


def test_block_matmul_params():
    # q 8*2*4 + k,v 2*8*1*4 + o 2*4*8 + MLP 3*8*16
    assert costs.block_matmul_params(M) == 64 + 64 + 64 + 384


def test_causal_pairs():
    assert costs.causal_pairs(0, 3) == 1 + 2 + 3
    assert costs.causal_pairs(5, 2) == 6 + 7


@pytest.mark.parametrize("offset,n,want", [
    # 2 FLOPs x 3 tokens x 2 layers x 576, attention 4*2*2*4 per pair over
    # 6 pairs, one head row 2*8*32
    (0, 3, 2 * 3 * 2 * 576 + 64 * 6 + 512),
    # 2 tokens after 5 cached: pairs 6 + 7
    (5, 2, 2 * 2 * 2 * 576 + 64 * 13 + 512),
])
def test_prefill_flops(offset, n, want):
    assert costs.prefill_flops(M, offset, n) == want


def test_decode_flops():
    # two rows, contexts 5 and 7: (blocks + head) per row, attention at 12
    assert costs.decode_flops(M, [5, 7]) == \
        2 * 2 * (2 * 576 + 256) + 64 * 12


def test_bytes():
    w = 2 * (2 * (576 + 16) + 8 + 256)
    assert costs.weight_bytes(M) == w
    assert costs.kv_bytes_per_token(M) == 2 * 2 * 1 * 4 * 2
    assert costs.decode_bytes(M, [5, 7]) == w + 32 * 12
    assert costs.prefill_bytes(M, 5, 2) == w + 32 * 7
    assert costs.seal_bytes(M, pages=3, page_size=4) == 2 * 3 * 4 * 32
    # KV of 12 positions plus bf16 queries and outputs of 2 rows
    assert costs.paged_attention_bytes(M, [5, 7]) == \
        32 * 12 + 2 * 2 * 2 * 2 * 4 * 2


def test_matmul_flops_match_xla_count():
    """The block's matmul count equals what XLA counts for the same
    einsums on the CPU."""
    import jax
    import jax.numpy as jnp
    d, H, K, hd, ff = 8, 2, 1, 4, 16
    x = jnp.ones((3, d))

    def block(x, wq, wk, wv, wo, wi, wg, wd):
        q = jnp.einsum("cd,dhk->chk", x, wq)
        k = jnp.einsum("cd,dgk->cgk", x, wk)
        v = jnp.einsum("cd,dgk->cgk", x, wv)
        o = jnp.einsum("chk,hkd->cd", q, wo)
        h = jnp.einsum("cd,df->cf", o, wi) * jnp.einsum("cd,df->cf", o, wg)
        return jnp.einsum("cf,fd->cd", h, wd), k, v

    ws = [jnp.ones(s) for s in [(d, H, hd), (d, K, hd), (d, K, hd),
                                (H, hd, d), (d, ff), (d, ff), (ff, d)]]
    ca = jax.jit(block).lower(x, *ws).compile().cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    # the elementwise product adds 3*16 FLOPs beside the matmuls
    one = dict(M, num_layers=1)
    assert ca["flops"] == 2 * 3 * costs.block_matmul_params(one) + 3 * ff


def test_peaks():
    p = peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert set(PEAKS) == {"TPU v5 lite"}
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
