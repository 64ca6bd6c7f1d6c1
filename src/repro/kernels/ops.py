"""Jitted public wrappers for the Pallas kernels.

On TPU the kernels always compile natively. Off TPU (the CPU tests) the
memory-movement and decode-attention wrappers run their jitted pure-jnp
references and flash prefill runs in interpret mode — the kernel body in
Python with the same BlockSpec semantics.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import ref
from repro.kernels.kv_gather import kv_gather_pallas
from repro.kernels.kv_scatter import kv_scatter_layer_pallas, kv_scatter_pallas
from repro.kernels.flash_prefill import flash_prefill_pallas
from repro.kernels.paged_attention import paged_attention_pallas


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=())
def _kv_gather_ref(storage, idx):
    return ref.kv_gather(storage, idx)


# kv_gather / kv_scatter are pure memory movement: the ref path is
# bitwise-identical to the kernels, so off-TPU (where the Pallas kernel
# would run through the grid interpreter — ~1s per transfer on the
# serving hot path, even jitted) the jitted ref implementation IS the
# data path. The kernels stay differentially tested against the same
# ref in tests/test_kernels.py and compile natively on TPU.
_kv_scatter_ref = jax.jit(ref.kv_scatter)


def kv_gather(storage: jax.Array, idx: jax.Array) -> jax.Array:
    if _interpret():
        return _kv_gather_ref(storage, idx)
    return kv_gather_pallas(storage, idx, interpret=False)


def kv_scatter(storage: jax.Array, buf: jax.Array,
               idx: jax.Array) -> jax.Array:
    if _interpret():
        return _kv_scatter_ref(storage, buf.astype(storage.dtype), idx)
    return kv_scatter_pallas(storage, buf, idx, interpret=False)


# Per-layer-triggered transfer (paper Fig. 10): move ONE layer's stripe
# of the linearized buffer while later layers are still prefilling. The
# sender takes the layer slice OUTSIDE the kernel (a lax.slice on the
# leading axis), so the whole-buffer gather kernel serves it too.

def kv_gather_layer(storage: jax.Array, idx: jax.Array,
                    layer: int) -> jax.Array:
    """storage: (L, NB, BS, W) -> (n*BS, W) stripe of ``layer``."""
    return kv_gather(lax.slice_in_dim(storage, layer, layer + 1, axis=0),
                     idx)[0]


# The receiver is one jitted program per (pool shape, block count): the
# layer is a traced operand, so every layer's scatter of an n-block
# stripe reuses the same build. The pool is donated and the program
# writes only the n destination blocks in place — no layer slice, no
# write-back of the layer into the pool. On TPU the body is the
# per-layer Pallas kernel (pool aliased to the output); off TPU it is
# the bitwise jnp ref, so the CPU tests cover the same wrapper and the
# same donation.
@partial(jax.jit, donate_argnums=0)
def kv_scatter_layer(storage: jax.Array, buf: jax.Array, idx: jax.Array,
                     layer: jax.Array) -> jax.Array:
    """Scatter one layer's (n*BS, W) stripe into blocks ``idx`` of
    ``layer`` of the donated (L, NB, BS, W) pool; returns the pool."""
    buf = buf.astype(storage.dtype)
    if _interpret():
        n, (_, _, bs, w) = idx.shape[0], storage.shape
        return storage.at[layer, idx].set(buf.reshape(n, bs, w))
    return kv_scatter_layer_pallas(storage, buf, idx, layer,
                                   interpret=False)


# Decode attention routes like kv_gather/kv_scatter: off-TPU the jitted
# pure-jnp ref IS the data path (the Pallas grid interpreter re-traces
# the whole page loop per call on the decode hot loop), on TPU the
# kernel compiles natively. ``paged_attention_inline`` is the traceable
# form for use INSIDE an enclosing jit (the fused decode step): same
# math, no nested jit boundary — so the eager per-layer loop and the
# fused step share bitwise-identical attention on every backend.
_paged_attention_ref = jax.jit(ref.paged_attention)


def paged_attention_inline(q: jax.Array, kv_pages: jax.Array,
                           block_table: jax.Array,
                           lens: jax.Array) -> jax.Array:
    if _interpret():
        return ref.paged_attention(q, kv_pages, block_table, lens)
    return paged_attention_pallas(q, kv_pages, block_table, lens,
                                  interpret=False)


def paged_attention(q: jax.Array, kv_pages: jax.Array,
                    block_table: jax.Array, lens: jax.Array) -> jax.Array:
    if _interpret():
        return _paged_attention_ref(q, kv_pages, block_table, lens)
    return paged_attention_pallas(q, kv_pages, block_table, lens,
                                  interpret=False)


def flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array,
                  q_offset: int = 0, prefix_pad: int = 0,
                  q_valid: int = 0) -> jax.Array:
    """q_offset > 0: suffix-only (chunked) prefill against a reused
    prefix KVCache — k/v cover prefix_pad + s positions (prefix_pad
    defaults to q_offset; larger = a right-padded prefix bucket whose
    padded keys are masked). q_valid > 0: only the first q_valid query
    rows are real; padded queries attend to nothing (output 0)."""
    return flash_prefill_pallas(q, k, v, interpret=_interpret(),
                                q_offset=q_offset, prefix_pad=prefix_pad,
                                q_valid=q_valid)
