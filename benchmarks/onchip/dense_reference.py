"""Plain float32 reference of a dense pre-norm decoder, and its fp8 control.

Written from the block equations, with nothing taken from the served
program but the weights this benchmark drew itself:

    h = x * rsqrt(mean(x^2) + eps) * g1
    q, k, v = h Wq, h Wk, h Wv;  RoPE on q and k (halves rotated, full head)
    a = softmax(q k^T / sqrt(hd), causal) v  (query head i reads KV i // rep)
    x = x + a Wo
    h = x * rsqrt(mean(x^2) + eps) * g2
    x = x + (silu(h Wg) * (h Wi)) Wd
    logits = rmsnorm(x, g_f) H     (H = embed^T when tied)

Every matmul runs at ``precision=HIGHEST``. The forward pass walks a
sequence in chunks of ``CHUNK`` positions against an f32 KV cache, so one
compiled program serves every sequence of a cell and the attention scores
of a chunk are the largest temporary.

``quant="fp8"`` is the control: the same pass with the inputs of every
projection, MLP and head matmul rounded to float8_e4m3 (activations per
row, weights per output channel), the precision step below the bfloat16
the configurations state.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 512
HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _q8(x, axes):
    """Round ``x`` to float8_e4m3 with one scale per slice along ``axes``."""
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * freqs          # [C, d/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def cache_len(capacity: int) -> int:
    return -(-capacity // CHUNK) * CHUNK


@functools.lru_cache(maxsize=None)
def _forward(dims: Tuple, quant: str):
    (L, d, H, K, hd, ff, V, eps, theta, tied) = dims
    rep = H // K
    f32 = jnp.float32

    def mm(spec, x, w, w_axes):
        w = w.astype(f32)
        if quant == "fp8":
            x, w = _q8(x, (-1,)), _q8(w, w_axes)
        return jnp.einsum(spec, x, w, precision=HI)

    def layer(carry, xs):
        x, pos, offset = carry
        p, kc, vc = xs
        a = p["attn"]
        h = _rms(x, p["ln1"].astype(f32), eps)
        q = _rope(mm("cd,dhk->chk", h, a["wq"], (0,)), pos, theta)
        k = _rope(mm("cd,dgk->cgk", h, a["wk"], (0,)), pos, theta)
        v = mm("cd,dgk->cgk", h, a["wv"], (0,))
        kc = jax.lax.dynamic_update_slice(kc, k, (offset, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v, (offset, 0, 0))
        qg = q.reshape(q.shape[0], K, rep, hd)
        s = jnp.einsum("cgrd,sgd->grcs", qg, kc, precision=HI) / math.sqrt(hd)
        mask = jnp.arange(kc.shape[0])[None, :] <= pos[:, None]
        s = jnp.where(mask[None, None], s, -jnp.inf)
        att = jax.nn.softmax(s, axis=-1)
        ctx = jnp.einsum("grcs,sgd->cgrd", att, vc, precision=HI)
        ctx = ctx.reshape(ctx.shape[0], H, hd)
        x = x + mm("chk,hkd->cd", ctx, a["wo"], (0, 1))
        m = p["mlp"]
        h = _rms(x, p["ln2"].astype(f32), eps)
        g = jax.nn.silu(mm("cd,df->cf", h, m["wg"], (0,)))
        u = mm("cd,df->cf", h, m["wi"], (0,))
        x = x + mm("cf,fd->cd", g * u, m["wo"], (0,))
        return (x, pos, offset), (kc, vc)

    def fwd(w, kcache, vcache, tokens, offset, targets):
        pos = offset + jnp.arange(tokens.shape[0])
        x = w["embed"][tokens].astype(f32)
        (x, _, _), (kcache, vcache) = jax.lax.scan(
            layer, (x, pos, offset), (w["blocks"], kcache, vcache))
        h = _rms(x, w["ln_f"].astype(f32), eps)
        if tied:
            logits = mm("cd,vd->cv", h, w["embed"], (1,))
        else:
            logits = mm("cd,dv->cv", h, w["head"], (0,))
        top = jnp.argmax(logits, -1)
        gap = jnp.max(logits, -1) - jnp.take_along_axis(
            logits, targets[:, None], -1)[:, 0]
        return gap, top, kcache, vcache

    return jax.jit(fwd, donate_argnums=(1, 2))


def _dims(m: Dict) -> Tuple:
    return (m["num_layers"], m["d_model"], m["num_heads"], m["num_kv_heads"],
            m["head_dim"], m["d_ff"], m["vocab_size"], float(m["norm_eps"]),
            float(m["rope_theta"]), bool(m["tie_embeddings"]))


def teacher_forced(weights, m: Dict, capacity: int, prompt: Sequence[int],
                   served: Sequence[int], quant: str = "f32",
                   targets: Sequence[int] = None) -> Tuple[np.ndarray,
                                                           np.ndarray]:
    """Run the reference over ``prompt + served`` and return, for each
    served token j, the gap by which ``targets[j]`` (default: the served
    token) lies below the best logit at the position that predicts it, and
    the token the reference puts first there."""
    fwd = _forward(_dims(m), quant)
    P, G = len(prompt), len(served)
    seq = list(prompt) + list(served)
    tgt = list(targets) if targets is not None else list(served)
    n_in = P + G - 1                        # the last served token is not fed
    S = cache_len(capacity)
    assert n_in <= S, (n_in, S)
    nxt = np.zeros(S + 1, np.int64)         # target of position t: seq[t+1]
    nxt[:len(seq) - 1] = seq[1:]
    nxt[P - 1:P - 1 + G] = tgt
    toks = np.zeros(S, np.int32)
    toks[:n_in] = seq[:n_in]
    shape = (m["num_layers"], S, m["num_kv_heads"], m["head_dim"])
    kc = jnp.zeros(shape, jnp.float32)
    vc = jnp.zeros(shape, jnp.float32)
    gaps, tops = [], []
    for off in range(0, n_in, CHUNK):
        g, t, kc, vc = fwd(weights, kc, vc,
                           jnp.asarray(toks[off:off + CHUNK]),
                           jnp.int32(off),
                           jnp.asarray(nxt[off:off + CHUNK].astype(np.int32)))
        gaps.append(np.asarray(g))
        tops.append(np.asarray(t))
    gaps, tops = np.concatenate(gaps), np.concatenate(tops)
    return gaps[P - 1:P - 1 + G], tops[P - 1:P - 1 + G]


def served_gaps(weights, m: Dict, capacity: int,
                sample: List[Tuple[Sequence[int], Sequence[int]]],
                plan: Optional[Dict] = None) -> List[np.ndarray]:
    """The f32 reference's gap for every served token of every
    ``(prompt, served)`` pair. ``plan`` is how the engine served them
    (``stage_blocks``, ``seal_boundary``): this reference has no cuts, so
    it takes an unsealed plan and refuses a sealed one, whose int8 seal
    changes the logits at every cut by design."""
    if plan is not None and plan.get("seal_boundary"):
        raise ValueError(
            f"the engine sealed the activations at the cuts of stages "
            f"{tuple(plan['stage_blocks'])}; dense_reference has no cuts. A "
            f"configuration served sealed names a reference module that "
            f"rounds at the same cuts (one scale per row, max(absmax, "
            f"1e-6) / 127, then clip(round(x / scale), -127, 127) * scale)")
    return [teacher_forced(weights, m, capacity, p, s)[0] for p, s in sample]


def control_gaps(weights, m: Dict, capacity: int,
                 sample: List[Tuple[Sequence[int], Sequence[int]]]
                 ) -> List[np.ndarray]:
    """The control: at each served position, the token the fp8 pass puts
    first, read by its gap under the f32 reference."""
    out = []
    for p, s in sample:
        _, top8 = teacher_forced(weights, m, capacity, p, s, quant="fp8")
        out.append(teacher_forced(weights, m, capacity, p, s,
                                  targets=top8)[0])
    return out
