"""The dense decoder: GQA attention with RoPE and a gated SiLU MLP in every
layer, RMSNorm before each, an untied or tied output head.

The weight tree has the layout the serving program takes (``embed``,
``final_norm``, optional ``lm_head``, and ``blocks/sub0`` with every
layer's matrices stacked on a leading axis). ``logits`` is the plain
reference: full attention over the whole sequence, no paged pool, no
kernel, no cache, no batching, and nothing of the serving program.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Iterable, Optional

import jax
import jax.numpy as jnp


def dims(c: Dict) -> Dict[str, int]:
    """Sizes of a configuration file, under short names."""
    hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    return {"d": c["hidden_size"], "nh": c["num_attention_heads"],
            "nkv": c["num_key_value_heads"], "hd": hd,
            "ff": c["intermediate_size"], "vocab": c["vocab_size"],
            "layers": c["num_hidden_layers"],
            "tied": bool(c.get("tie_word_embeddings", False))}


# ------------------------------------------------------------- the program
def program_config(c: Dict):
    """The serving program's config for a configuration file."""
    from repro.models.config import ModelConfig
    m = dims(c)
    return ModelConfig(
        name=c["name"], arch_type="dense", num_layers=m["layers"],
        d_model=m["d"], num_heads=m["nh"], num_kv_heads=m["nkv"],
        d_ff=m["ff"], vocab_size=m["vocab"], head_dim=m["hd"],
        qkv_bias=bool(c.get("attention_bias", False)),
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
        tie_embeddings=m["tied"],
        max_seq_len=int(c["max_position_embeddings"]))


# ---------------------------------------------------------------- weights
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


def init_leaf(path, shape, key, dtype):
    """Every leaf is a matrix or a norm gain."""
    return None


# -------------------------------------------------------------- reference
def logits(c: Dict, params, tokens: Optional[jax.Array], *,
           hidden: Optional[jax.Array] = None,
           pos: Optional[jax.Array] = None) -> jax.Array:
    """(b, s) int32 tokens -> (b, s, vocab) float32 logits. With
    ``hidden`` (b, s, d) given instead, the layers alone run on it and the
    hidden state comes back (the control runs layer by layer)."""
    m = dims(c)
    b, s = (tokens if hidden is None else hidden).shape[:2]
    nh, nkv, hd = m["nh"], m["nkv"], m["hd"]
    half = hd // 2
    inv_freq = c["rope_theta"] ** (-jnp.arange(half, dtype=jnp.float32)
                                   / half)
    if pos is None:
        pos = jnp.arange(s)
    ang = pos.astype(jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    causal = jnp.tril(jnp.ones((s, s), bool))
    eps = c["rms_norm_eps"]
    dt = (params["embed"] if hidden is None else hidden).dtype

    def norm(x, w):
        xf = x.astype(jnp.float32)
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        return (xf * jax.lax.rsqrt(ms + eps)).astype(dt) * w

    def rotate(x):                              # (b, s, heads, hd)
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1).astype(dt)

    def layer(h, p):
        x = norm(h, p["norm"])
        q, k, v = x @ p["wq"], x @ p["wk"], x @ p["wv"]
        q = rotate(q.reshape(b, s, nh, hd))
        k = rotate(k.reshape(b, s, nkv, hd))
        v = v.reshape(b, s, nkv, hd)
        k = jnp.repeat(k, nh // nkv, axis=2)
        v = jnp.repeat(v, nh // nkv, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
        sc = jnp.where(causal, sc / math.sqrt(hd), -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1).astype(dt)
        o = jnp.einsum("bhqk,bkhd->bqhd", pr, v)
        h = h + o.reshape(b, s, nh * hd) @ p["wo"]
        x = norm(h, p["norm2"])
        f = p["mlp"]
        h = h + (jax.nn.silu(x @ f["w_gate"]) * (x @ f["w_up"])) @ f["w_down"]
        return h, None

    h = params["embed"][tokens] if hidden is None else hidden
    h, _ = jax.lax.scan(layer, h, params["blocks"]["sub0"])
    if hidden is not None:
        return h
    h = norm(h, params["final_norm"])
    head = params["embed"].T if m["tied"] else params["lm_head"]
    return (h @ head).astype(jnp.float32)


# ---------------------------------------------------------------- control
def control_embed(c: Dict, params, tokens: jax.Array) -> jax.Array:
    return params["embed"][tokens]


def control_layers(c: Dict, params) -> Iterable:
    blocks = params["blocks"]["sub0"]
    for layer in range(dims(c)["layers"]):
        yield jax.tree.map(lambda x: x[layer], blocks)


def control_layer(c: Dict, p, h: jax.Array, pos: jax.Array) -> jax.Array:
    one = jax.tree.map(lambda x: x[None], p)
    return logits(c, {"blocks": {"sub0": one}}, None, hidden=h, pos=pos)


def control_head(c: Dict, dtype, h: jax.Array, params) -> jax.Array:
    head = params["embed"].T if dims(c)["tied"] else params["lm_head"]
    return _control_head(c, dtype, h, params["final_norm"], head)


@partial(jax.jit, static_argnames=("c", "dtype"))
def _control_head(c, dtype, h, final_norm, head):
    hf = h.astype(jnp.float32)
    ms = jnp.mean(hf * hf, axis=-1, keepdims=True)
    x = (hf * jax.lax.rsqrt(ms + c["rms_norm_eps"])).astype(dtype) \
        * final_norm.astype(dtype)
    return jnp.argmax((x @ head.astype(dtype)).astype(jnp.float32), -1)


# ------------------------------------------------------------------- work
# A multiply-add counts as two operations. Only what the algorithm needs
# is counted: matrix products with the weights, attention over the tokens
# that are really there (causal in prefill), and the bytes of weights and
# of live keys and values read. Padding, masked positions and rows of
# empty slots are not work and are not counted.
def matmul_params(c: Dict, head: bool = True) -> int:
    """Weights a token is multiplied by: every layer's projections and
    MLP, and the output head if ``head``."""
    m = dims(c)
    d, q, kv = m["d"], m["nh"] * m["hd"], m["nkv"] * m["hd"]
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * m["ff"]
    return m["layers"] * per_layer + (d * m["vocab"] if head else 0)


def weight_bytes(c: Dict, itemsize: int = 4) -> int:
    """Bytes of every weight the decode step reads (embedding rows for a
    batch are negligible and not counted)."""
    return matmul_params(c) * itemsize


def kv_bytes_per_token(c: Dict, itemsize: int = 4) -> int:
    """Key and value bytes one token holds, over all layers."""
    m = dims(c)
    return m["layers"] * 2 * m["nkv"] * m["hd"] * itemsize


def prefill_flops(c: Dict, lens: Iterable[int]) -> float:
    """Prompts of these lengths, each run once: every token through the
    layers, causal attention, the head for the last token only."""
    m = dims(c)
    body = matmul_params(c, head=False)
    head = m["d"] * m["vocab"]
    total = 0.0
    for n in lens:
        attn = 2 * m["nh"] * m["hd"] * n * (n + 1) * m["layers"]
        total += 2.0 * body * n + attn + 2.0 * head
    return total


def decode_flops(c: Dict, ctx: Iterable[int]) -> float:
    """Decoded tokens that attend over these context lengths: each token
    through every weight and the head, plus attention over its context."""
    m = dims(c)
    per_tok = 2.0 * matmul_params(c)
    attn = 4.0 * m["nh"] * m["hd"] * m["layers"]
    return sum(per_tok + attn * n for n in ctx)


def paged_attention_need(c: Dict, ctx: Iterable[int],
                         itemsize: int = 4) -> Dict[str, float]:
    """Least work of the decode attention kernel for these live contexts,
    over all layers: the keys and values of live tokens read once, the
    query read and the output written, and 4 * heads * head_dim
    operations per key (scores and weighted values)."""
    m = dims(c)
    kv = 2 * m["nkv"] * m["hd"] * itemsize
    qo = 2 * m["nh"] * m["hd"] * itemsize
    flops = bytes_ = 0.0
    for n in ctx:
        flops += 4.0 * m["nh"] * m["hd"] * n * m["layers"]
        bytes_ += (kv * n + qo) * m["layers"]
    return {"flops": flops, "bytes": bytes_}
