"""Pallas kernels vs jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref as KR
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import pages_per_block
from repro.kernels.seal import seal_pallas, unseal_pallas
from repro.kernels import ops as KO


@pytest.mark.parametrize("shape,dtype", [
    ((64, 128), jnp.float32),
    ((256, 512), jnp.bfloat16),
    ((100, 48), jnp.float32),
    ((8, 2048), jnp.bfloat16),
    ((1, 16), jnp.float32),
])
def test_seal_kernel_matches_oracle(shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32).astype(dtype)
    key, ctr = jnp.uint32(0xDEADBEEF), jnp.uint32(7)
    c1, s1 = seal_pallas(x, key, ctr)
    c2, s2 = KR.seal_ref(x, key, ctr)
    # identical up to rare round-to-even ties at the quantization boundary
    assert (np.asarray(c1) != np.asarray(c2)).mean() < 1e-3
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)
    y = unseal_pallas(c1, s1, key, ctr, out_dtype=jnp.float32)
    xf = np.asarray(x, np.float32)
    err = np.abs(np.asarray(y) - xf).max() / (np.abs(xf).max() + 1e-9)
    assert err < 0.01


def test_ciphertext_statistics_uniform():
    x = jax.random.normal(jax.random.PRNGKey(2), (256, 512), jnp.float32)
    c, _ = KR.seal_ref(x, jnp.uint32(3), jnp.uint32(1))
    h = np.bincount(np.asarray(c).ravel(), minlength=256)
    chi2 = ((h - h.mean()) ** 2 / h.mean()).sum()
    assert chi2 < 400          # ~255 dof; catastrophic non-uniformity fails


def test_counter_separation():
    """Same plaintext under different counters -> unrelated ciphertexts."""
    x = jax.random.normal(jax.random.PRNGKey(3), (32, 64), jnp.float32)
    c1, _ = KR.seal_ref(x, jnp.uint32(9), jnp.uint32(0))
    c2, _ = KR.seal_ref(x, jnp.uint32(9), jnp.uint32(1))
    assert (np.asarray(c1) == np.asarray(c2)).mean() < 0.05


@pytest.mark.parametrize("B,H,S,D,win,causal", [
    (2, 4, 256, 64, 0, True),
    (1, 2, 128, 32, 64, True),
    (2, 2, 64, 16, 0, True),
    (1, 1, 512, 64, 0, True),
    (1, 2, 128, 32, 0, False),
])
def test_flash_kernel_matches_oracle(B, H, S, D, win, causal):
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (B * H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B * H, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B * H, S, D), jnp.float32)
    out = flash_attention_pallas(q, k, v, causal=causal, window=win)
    ref = KR.flash_attention_ref(
        q.reshape(B, H, S, D), k.reshape(B, H, S, D), v.reshape(B, H, S, D),
        causal=causal, window=win).reshape(B * H, S, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


def test_flash_gqa_wrapper():
    B, S, H, KVH, D = 2, 64, 8, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KVH, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KVH, D), jnp.float32)
    a = KO.flash_attention(q, k, v, causal=True, use_kernel=True)
    b = KO.flash_attention(q, k, v, causal=True, use_kernel=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# Paged decode attention: length-bounded kernel vs page-gather oracle
# ---------------------------------------------------------------------------
# A kernel row and an oracle row are both f32 values rounded to the pool's
# dtype: f32 agrees to 3e-5, bf16 to one unit in the last place (2**-7).
TOL = {np.float32: dict(atol=3e-5, rtol=3e-5),
       jnp.bfloat16: dict(atol=1e-5, rtol=2.0 ** -7)}


def _paged_case(seed, B, KVH, rep, D, Pg, MP, dtype=np.float32):
    rng = np.random.RandomState(seed)
    N = B * MP + 1                       # page 0 reserved null

    def draw(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32), dtype)

    q = draw(B, KVH * rep, D)
    kp = draw(N, KVH, Pg, D)
    vp = draw(N, KVH, Pg, D)
    # distinct non-null pages per row, shuffled (layout independence)
    bt = rng.permutation(N - 1)[: B * MP].reshape(B, MP).astype(np.int32) + 1
    sl = rng.randint(1, MP * Pg + 1, size=B).astype(np.int32)
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(sl)


def _ragged_lens(B, KVH, D, Pg, MP, dtype):
    """Idle, one token, a partial page, exactly one and two blocks of the
    kernel's ``ppb`` pages, and a full table (the last row)."""
    ppb = pages_per_block(Pg, KVH, D, jnp.dtype(dtype).itemsize, MP)
    lens = [0, 1, Pg + 3, Pg * ppb, 2 * Pg * ppb][: B - 1] + [MP * Pg]
    assert len(lens) == B and max(lens) == MP * Pg
    return jnp.asarray(lens, jnp.int32)


def _assert_rows_match(ker, ref, sl, dtype):
    """Rows with tokens match the oracle; idle rows are finite."""
    ker = np.asarray(ker, np.float32)
    ref = np.asarray(ref, np.float32)
    live = np.asarray(sl) > 0
    np.testing.assert_allclose(ker[live], ref[live], **TOL[dtype])
    assert np.isfinite(ker).all()


@pytest.mark.parametrize("B,KVH,rep,D,Pg,MP,dtype,ragged", [
    pytest.param(3, 2, 4, 16, 4, 5, np.float32, False, id="3-2-4-16-4-5"),
    pytest.param(2, 4, 1, 32, 8, 3, np.float32, False, id="2-4-1-32-8-3"),
    pytest.param(1, 1, 2, 64, 16, 2, np.float32, False, id="1-1-2-64-16-2"),
    pytest.param(4, 2, 2, 128, 8, 4, np.float32, False, id="4-2-2-128-8-4"),
    # GLM-4-9B (KVH 2, rep 16, D 128) and llama3.2-1b (KVH 8, rep 4, D 64)
    # at page size 16 in bf16: several blocks a row, ragged lengths
    pytest.param(6, 2, 16, 128, 16, 72, jnp.bfloat16, True,
                 id="glm4-9b-bf16-ragged"),
    pytest.param(6, 8, 4, 64, 16, 36, jnp.bfloat16, True,
                 id="llama3.2-1b-bf16-ragged"),
    pytest.param(4, 2, 16, 128, 16, 40, jnp.bfloat16, False,
                 id="glm4-9b-bf16"),
    pytest.param(4, 8, 4, 64, 16, 20, jnp.bfloat16, False,
                 id="llama3.2-1b-bf16"),
    pytest.param(5, 2, 2, 32, 8, 9, np.float32, True, id="lane-packed-ragged"),
])
def test_paged_kernel_matches_oracle(B, KVH, rep, D, Pg, MP, dtype, ragged):
    q, kp, vp, bt, sl = _paged_case(7, B, KVH, rep, D, Pg, MP, dtype)
    if ragged:
        sl = _ragged_lens(B, KVH, D, Pg, MP, dtype)
    ker = KO.paged_attention(q, kp, vp, bt, sl, use_kernel=True)
    ref = KO.paged_attention(q, kp, vp, bt, sl, use_kernel=False)
    assert ker.dtype == ref.dtype == q.dtype
    _assert_rows_match(ker, ref, sl, dtype)


def test_paged_kernel_idle_rows_are_finite():
    """seq_len == 0 rows (idle slots parked on the null page) must produce
    finite garbage, not NaNs that could poison downstream reductions."""
    q, kp, vp, bt, sl = _paged_case(8, 3, 2, 2, 16, 4, 3)
    sl = sl.at[1].set(0)
    for use_kernel in (False, True):
        out = KO.paged_attention(q, kp, vp, bt, sl, use_kernel=use_kernel)
        assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("B,KVH,rep,D,Pg,MP,shared,dtype", [
    pytest.param(3, 2, 4, 16, 4, 5, 2, np.float32, id="3-2-4-16-4-5-2"),
    pytest.param(4, 2, 2, 32, 8, 4, 3, np.float32, id="4-2-2-32-8-4-3"),
    pytest.param(2, 1, 2, 64, 16, 3, 1, np.float32, id="2-1-2-64-16-3-1"),
    # the shared prefix ends inside a row's second block of ppb = 32 pages
    # (GLM) or 16 (llama), so that block reads shared, then private pages
    pytest.param(3, 2, 16, 128, 16, 48, 37, jnp.bfloat16,
                 id="glm4-9b-bf16-shared-37"),
    pytest.param(3, 8, 4, 64, 16, 24, 19, jnp.bfloat16,
                 id="llama3.2-1b-bf16-shared-19"),
])
def test_paged_kernel_shared_tables_parity(B, KVH, rep, D, Pg, MP, shared,
                                           dtype):
    """COW prefix sharing aliases block-table entries: several rows point at
    the SAME physical prefix pages (demand paging, DESIGN.md §Demand
    paging). The gather path is indifferent to aliasing by construction;
    sweep kernel vs oracle over shared tables to pin that down."""
    rng = np.random.RandomState(11)
    # `shared` common prefix pages + per-row private tails
    N = shared + B * (MP - shared) + 1

    def draw(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32), dtype)

    q = draw(B, KVH * rep, D)
    kp = draw(N, KVH, Pg, D)
    vp = draw(N, KVH, Pg, D)
    bt = np.zeros((B, MP), np.int32)
    nxt = shared + 1
    for b in range(B):
        bt[b, :shared] = np.arange(1, shared + 1)     # aliased prefix
        for pi in range(shared, MP):
            bt[b, pi] = nxt
            nxt += 1
    # every row covers the shared prefix and some of its private tail
    sl = rng.randint(shared * Pg + 1, MP * Pg + 1, size=B).astype(np.int32)
    bt, sl = jnp.asarray(bt), jnp.asarray(sl)
    ker = KO.paged_attention(q, kp, vp, bt, sl, use_kernel=True)
    ref = KO.paged_attention(q, kp, vp, bt, sl, use_kernel=False)
    _assert_rows_match(ker, ref, sl, dtype)
    # aliasing really is invisible: materializing each row's pages into a
    # private copy of the pool changes nothing
    for b in range(B):
        priv_bt = jnp.asarray(np.arange(1, MP + 1, dtype=np.int32))[None]
        priv_kp = jnp.concatenate([kp[:1], kp[bt[b]]], axis=0)
        priv_vp = jnp.concatenate([vp[:1], vp[bt[b]]], axis=0)
        one = KO.paged_attention(q[b:b + 1], priv_kp, priv_vp, priv_bt,
                                 sl[b:b + 1], use_kernel=False)
        np.testing.assert_allclose(np.asarray(one, np.float32)[0],
                                   np.asarray(ref, np.float32)[b],
                                   atol=2e-5, rtol=2e-5)


def test_paged_oracle_matches_dense_decode_attention():
    """Packing a dense [B, KVH, S, D] cache into pages must reproduce
    decode_attention row-for-row (same math, block-table indirection)."""
    from repro.models import layers as L
    rng = np.random.RandomState(9)
    B, H, KVH, D, S, Pg = 3, 4, 2, 16, 24, 4
    MP = S // Pg
    q4 = jnp.asarray(rng.randn(B, 1, H, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, KVH, S, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, KVH, S, D).astype(np.float32))
    lens = np.asarray([5, 24, 17], np.int32)
    # pack: row b's position t -> page 1 + b*MP + t//Pg, offset t%Pg
    kp = np.zeros((1 + B * MP, KVH, Pg, D), np.float32)
    vp = np.zeros_like(kp)
    bt = np.zeros((B, MP), np.int32)
    for b in range(B):
        for pi in range(MP):
            pid = 1 + b * MP + pi
            bt[b, pi] = pid
            kp[pid] = np.asarray(k)[b, :, pi * Pg:(pi + 1) * Pg]
            vp[pid] = np.asarray(v)[b, :, pi * Pg:(pi + 1) * Pg]
    paged = KR.paged_attention_ref(q4[:, 0], jnp.asarray(kp),
                                   jnp.asarray(vp), jnp.asarray(bt),
                                   jnp.asarray(lens))
    for b in range(B):
        dense = L.decode_attention(q4[b:b + 1], k[b:b + 1], v[b:b + 1],
                                   jnp.int32(lens[b]))
        np.testing.assert_allclose(np.asarray(paged)[b],
                                   np.asarray(dense)[0, 0],
                                   atol=2e-5, rtol=2e-5)


def test_seal_bf16_dtypes():
    x = jax.random.normal(jax.random.PRNGKey(6), (64, 64), jnp.float32)
    c, s = KR.seal_ref(x.astype(jnp.bfloat16), jnp.uint32(1), jnp.uint32(2))
    y = KR.unseal_ref(c, s, jnp.uint32(1), jnp.uint32(2), jnp.bfloat16)
    assert y.dtype == jnp.bfloat16
