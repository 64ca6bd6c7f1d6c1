"""Seeded weights, made on the device in one call.

The tree's layout is the family's (``families.of(c).shapes``), the one
the serving program takes. The benchmark makes it, so the plain
reference and the program read the same numbers and neither made them.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from bench import families

STD = 0.02


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def make(c: Dict, seed: int, dtype=jnp.float32):
    """Normal(0, STD) matrices and unit norm gains from ``seed``, and the
    family's values for any other leaf, built by one jitted call so they
    never pass through the host."""
    fam = families.of(c)
    tree = fam.shapes(c)
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_shape)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(tree, is_leaf=_is_shape)[0]]

    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for path, shape, k in zip(paths, leaves, keys):
            own = fam.init_leaf(path, shape, k, dtype)
            if own is not None:
                out.append(own)
            elif "norm" in path:
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
