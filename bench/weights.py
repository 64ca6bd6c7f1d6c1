"""Seeded weights for a dense decoder, made on the device in one call.

The tree has the layout the serving program takes (``embed``,
``final_norm``, optional ``lm_head``, and ``blocks/sub0`` with every
layer's matrices stacked on a leading axis). The benchmark makes it, so
the plain reference and the program read the same numbers and neither
made them.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

STD = 0.02


def dims(c: Dict) -> Dict[str, int]:
    """Sizes of a configuration file, under short names."""
    hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    return {"d": c["hidden_size"], "nh": c["num_attention_heads"],
            "nkv": c["num_key_value_heads"], "hd": hd,
            "ff": c["intermediate_size"], "vocab": c["vocab_size"],
            "layers": c["num_hidden_layers"],
            "tied": bool(c.get("tie_word_embeddings", False))}


def shapes(c: Dict) -> Dict:
    """Shape of every leaf of the weight tree."""
    m = dims(c)
    d, L, ff = m["d"], m["layers"], m["ff"]
    q, kv = m["nh"] * m["hd"], m["nkv"] * m["hd"]
    t = {"embed": (m["vocab"], d), "final_norm": (d,),
         "blocks": {"sub0": {
             "norm": (L, d), "wq": (L, d, q), "wk": (L, d, kv),
             "wv": (L, d, kv), "wo": (L, q, d), "norm2": (L, d),
             "mlp": {"w_gate": (L, d, ff), "w_up": (L, d, ff),
                     "w_down": (L, ff, d)}}}}
    if not m["tied"]:
        t["lm_head"] = (d, m["vocab"])
    return t


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def make(c: Dict, seed: int, dtype=jnp.float32):
    """Normal(0, STD) matrices and unit norm gains from ``seed``, built by
    one jitted call so they never pass through the host."""
    tree = shapes(c)
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_shape)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(tree, is_leaf=_is_shape)[0]]

    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for path, shape, k in zip(paths, leaves, keys):
            if "norm" in path:
                out.append(jnp.ones(shape, dtype))
            else:
                out.append((jax.random.normal(k, shape, jnp.float32)
                            * STD).astype(dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(build)(key_for(seed))


def key_for(seed: int) -> jax.Array:
    """A PRNG key from any whole number, however large."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
