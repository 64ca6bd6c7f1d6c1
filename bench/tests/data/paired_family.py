"""A family for the tests: the dense decoder's layers held in blocks of two
positions, the program's ``layer_block = ("attn", "attn")``, so that
``blocks/sub0`` stacks layers 0, 2, 4, ... and ``blocks/sub1`` layers 1,
3, 5, ..., with the output head tied to the embedding. Its weight tree is
not the dense one; its layers and work are. It shows that a family goes
into a checkout as a new module and a configuration that names it."""
from __future__ import annotations

from typing import Dict, Iterable, Optional

import jax
import jax.numpy as jnp

from bench.families import dense

SUBS = ("sub0", "sub1")


def _half(c: Dict) -> Dict:
    """The configuration of one sub-position's stack: half the layers."""
    if c["num_hidden_layers"] % len(SUBS) or not c["tie_word_embeddings"]:
        raise ValueError("a paired configuration has an even number of "
                         "layers and a tied head")
    return {**c, "num_hidden_layers": c["num_hidden_layers"] // len(SUBS)}


def program_config(c: Dict):
    return dense.program_config(c).replace(layer_block=("attn",) * len(SUBS))


def shapes(c: Dict) -> Dict:
    t = dense.shapes(_half(c))
    t["blocks"] = {s: t["blocks"]["sub0"] for s in SUBS}
    return t


def init_leaf(path, shape, key, dtype):
    return None


def logits(c: Dict, params, tokens: Optional[jax.Array], *,
           hidden: Optional[jax.Array] = None,
           pos: Optional[jax.Array] = None) -> jax.Array:
    h = params["embed"][tokens] if hidden is None else hidden

    def pair(h, blk):
        for s in SUBS:
            h = dense.control_layer(c, blk[s], h, pos)
        return h, None

    h, _ = jax.lax.scan(pair, h, params["blocks"])
    if hidden is not None:
        return h
    hf = h.astype(jnp.float32)
    ms = jnp.mean(hf * hf, axis=-1, keepdims=True)
    x = (hf * jax.lax.rsqrt(ms + c["rms_norm_eps"])).astype(h.dtype) \
        * params["final_norm"]
    return (x @ params["embed"].T).astype(jnp.float32)


control_embed = dense.control_embed
control_layer = dense.control_layer
control_head = dense.control_head


def control_layers(c: Dict, params) -> Iterable:
    for layer in range(c["num_hidden_layers"]):
        s, j = SUBS[layer % len(SUBS)], layer // len(SUBS)
        yield jax.tree.map(lambda x: x[j], params["blocks"][s])


prefill_flops = dense.prefill_flops
decode_flops = dense.decode_flops
paged_attention_need = dense.paged_attention_need
