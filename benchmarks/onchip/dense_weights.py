"""Seeded weights for a dense pre-norm decoder, drawn on the device in one
jitted call, in the layout the served program takes and in the type it
serves (bfloat16).

Over a ``("pod", ...)`` mesh every leaf with a layer axis is drawn sharded
by layer over ``pod`` and the rest replicated, so no chip ever holds the
whole model: the layout a pipeline of one stage per pod takes as it is.
The values for a seed do not depend on the mesh (``jax_threefry_partitionable``
draws a sharded array as it draws a whole one).

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
from jax.sharding import NamedSharding, PartitionSpec as P


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


def init(m: Dict[str, Any], seed: int, dtype=jnp.bfloat16, mesh=None):
    """All weights of model ``m`` from ``seed``: on the default device, or
    with ``mesh`` the blocks sharded by layer over its ``pod`` axis and the
    embedding, final norm and head replicated."""
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

    if mesh is None:
        return jax.jit(draw)(base_key(seed))
    by_layer = NamedSharding(mesh, P("pod"))
    whole = NamedSharding(mesh, P())
    out = {k: jax.tree.map(lambda _: by_layer if k == "blocks" else whole,
                           v, is_leaf=_is_leaf) for k, v in spec.items()}
    return jax.jit(draw, out_shardings=out)(base_key(seed))
