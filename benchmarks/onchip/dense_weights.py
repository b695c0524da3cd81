"""Seeded weights for a dense pre-norm decoder, drawn on the device in one
jitted call, in the layout the served program takes and in the type it
serves (bfloat16).

Scales keep every logit of order one: projections are N(0, 1/fan_in), the
norms start at one, and the embedding and the head are N(0, 1/d_model), so
the tied head of Command-R and the separate head of GLM-4 give logits of
the same spread.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp


def base_key(seed: int):
    """A PRNG key for any whole ``seed``, also past 32 bits."""
    key = jax.random.PRNGKey(seed % 2**32)
    return jax.random.fold_in(key, (seed >> 32) % 2**31)


def shapes(m: Dict[str, Any]) -> Dict[str, Any]:
    """Shape and fan-in of every leaf, keyed like the program's params."""
    L, d, H, K, hd, ff, V = (m["num_layers"], m["d_model"], m["num_heads"],
                             m["num_kv_heads"], m["head_dim"], m["d_ff"],
                             m["vocab_size"])
    tree = {
        "embed": ((V, d), d),
        "ln_f": ((d,), None),
        "blocks": {
            "ln1": ((L, d), None),
            "ln2": ((L, d), None),
            "attn": {"wq": ((L, d, H, hd), d), "wk": ((L, d, K, hd), d),
                     "wv": ((L, d, K, hd), d), "wo": ((L, H, hd, d), H * hd)},
            "mlp": {"wi": ((L, d, ff), d), "wg": ((L, d, ff), d),
                    "wo": ((L, ff, d), ff)},
        },
    }
    if not m["tie_embeddings"]:
        tree["head"] = ((d, V), d)
    return tree


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def init(m: Dict[str, Any], seed: int, dtype=jnp.bfloat16):
    """All weights of model ``m`` from ``seed``, on the default device."""
    spec = shapes(m)
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_leaf)

    def draw(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (shape, fan_in) in zip(keys, leaves):
            if fan_in is None:
                out.append(jnp.ones(shape, dtype))
            else:
                std = 1.0 / math.sqrt(fan_in)
                out.append((jax.random.normal(k, shape, dtype)
                            * jnp.asarray(std, dtype)).astype(dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(draw)(base_key(seed))
