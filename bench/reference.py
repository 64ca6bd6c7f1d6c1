"""The plain reference a served stream is held to, and the comparison.

``logits`` is a causal forward of a dense decoder in plain ``jax.numpy``
from a configuration file's sizes and a weight tree: full attention over
the whole sequence, no paged pool, no kernel, no cache, no batching. It
imports nothing of the serving program. ``served_gaps`` runs it once over
a prompt and the tokens served for it, in float32 at the matrix-product
precision the configuration states (``matmul_precision``), and reads, at
every served position, how far the served token's logit lies below the
reference's best; the share of served tokens whose gap passes a
near-tie is the number a run is judged by (``harness.check``). With
``control``, it also reads the same gap for the token that the reference
computed in a lower precision (``control_tokens``) ranks first: the
control that the limit has to reject.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import dims

# Sequences are padded to a multiple of this, so the reference compiles
# for few lengths.
PAD = 512


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


@partial(jax.jit, static_argnames=("c",))
def _gaps(c, params, tokens, served, pick, mask):
    """Per position: the reference's best logit minus the logit of the
    served token, and minus the logit of the control's token ``pick``."""
    with jax.default_matmul_precision(c["matmul_precision"]):
        ref = logits(c, params, tokens)[0]
    best = ref.max(axis=-1)

    def gap(tok):
        g = best - jnp.take_along_axis(ref, tok[0][:, None], 1)[:, 0]
        return jnp.where(mask[0], g, 0.0)

    return {"served": gap(served), "control": gap(pick)}


@partial(jax.jit, static_argnames=("c", "dtype"))
def _control_layer(c, dtype, h, p, pos):
    """One layer of the reference with its weights cast to ``dtype``."""
    one = jax.tree.map(lambda x: x.astype(dtype)[None], p)
    return logits(c, {"blocks": {"sub0": one}}, None, hidden=h, pos=pos)


@partial(jax.jit, static_argnames=("c", "dtype"))
def _control_head(c, dtype, h, final_norm, head):
    hf = h.astype(jnp.float32)
    ms = jnp.mean(hf * hf, axis=-1, keepdims=True)
    x = (hf * jax.lax.rsqrt(ms + c["rms_norm_eps"])).astype(dtype) \
        * final_norm.astype(dtype)
    return jnp.argmax((x @ head.astype(dtype)).astype(jnp.float32), -1)


def control_tokens(c: Dict, params, tokens: jax.Array,
                   dtype=jnp.bfloat16) -> jax.Array:
    """The token the reference computed in ``dtype`` ranks first at every
    position: weights cast one layer at a time, so the control never holds
    a second copy of the model."""
    m = dims(c)
    fc = _frozen(c)
    h = params["embed"][tokens].astype(dtype)
    pos = jnp.arange(tokens.shape[1])
    blocks = params["blocks"]["sub0"]
    for layer in range(m["layers"]):
        p = jax.tree.map(lambda x: x[layer], blocks)
        h = _control_layer(fc, dtype, h, p, pos)
    head = params["embed"].T if m["tied"] else params["lm_head"]
    return _control_head(fc, dtype, h, params["final_norm"], head)


def _arrays(prompt: Sequence[int], served: Sequence[int]):
    """Token row (prompt ++ served[:-1]), the served token due at each
    position, and the mask of positions that produced a served token."""
    seq = list(prompt) + list(served[:-1])
    width = -(-len(seq) // PAD) * PAD
    toks = np.zeros((1, width), np.int32)
    toks[0, :len(seq)] = seq
    want = np.zeros((1, width), np.int32)
    mask = np.zeros((1, width), bool)
    p0 = len(prompt) - 1
    want[0, p0:p0 + len(served)] = served
    mask[0, p0:p0 + len(served)] = True
    return jnp.asarray(toks), jnp.asarray(want), jnp.asarray(mask)


def _frozen(c: Dict):
    """A hashable stand-in for a configuration file (a jit static arg)."""
    return _Frozen(tuple(sorted((k, v) for k, v in c.items()
                                if isinstance(v, (int, float, str, bool)))))


class _Frozen(dict):
    def __init__(self, items):
        super().__init__(items)
        self._key = items

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _Frozen) and self._key == other._key


def served_gaps(c: Dict, params, streams: List[tuple],
                control=None) -> Dict[str, List[np.ndarray]]:
    """For each (prompt, served tokens) pair, the gap of every served token
    below the reference's best logit (and, with ``control`` a dtype, the
    gap of the token the reference in that dtype ranks first at the same
    position), one request at a time."""
    fc = _frozen(c)
    out: Dict[str, List[np.ndarray]] = {"served": [], "control": []}
    for prompt, served in streams:
        toks, want, mask = _arrays(prompt, served)
        pick = want if control is None else \
            control_tokens(c, params, toks, control)
        g = _gaps(fc, params, toks, want, pick, mask)
        m = np.asarray(mask[0])
        out["served"].append(np.asarray(g["served"])[m])
        if control is not None:
            out["control"].append(np.asarray(g["control"])[m])
    return out


