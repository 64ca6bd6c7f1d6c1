"""Pallas kernels: RecvScatter — restore discrete KV blocks from bytes.

The C3 receiver hot path (paper §3.6): the contiguous buffer that arrived
over RDMA is scattered back into the receiver's paged pool at the
destination block table. Implemented as an *operator* (the paper's
flexibility option): the pool buffer is donated via input_output_aliases
so untouched pages keep their content and touched pages are overwritten
in place, without interrupting other operators in the stream.

Two forms share the kernel body:

* ``kv_scatter_pallas`` lands every layer of a whole linearized buffer
  (grid ``(L, n)``).
* ``kv_scatter_layer_pallas`` lands ONE layer's stripe (paper Fig. 10,
  per-layer-triggered transfer) into the full ``(L, NB, BS, W)`` pool.
  The layer and the block ids are scalar-prefetch operands, so one
  traced program serves every layer: grid step ``i`` writes stripe block
  ``i`` to ``(layer, idx[i])``. The pool operand stays in HBM
  (``pl.ANY``): only the ``n`` written blocks move, and the rest of the
  pool is neither read nor copied. ``ops.kv_scatter_layer`` wraps it in
  one jitted program per block count with the pool donated.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, pool_ref, buf_ref, out_ref):
    out_ref[0] = buf_ref[...]


def kv_scatter_pallas(storage: jax.Array, buf: jax.Array, idx: jax.Array, *,
                      interpret: bool = True) -> jax.Array:
    """storage: (L, NB, BS, W); buf: (L, n*BS, W); idx: (n,) int32.
    Returns the updated pool (same buffer, donated)."""
    L, NB, BS, W = storage.shape
    n = idx.shape[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(L, n),
        in_specs=[
            # the pool rides through untouched via aliasing; present it to
            # the kernel so the alias has a position in the operand list
            pl.BlockSpec((1, 1, BS, W),
                         lambda l, i, idx_ref: (l, idx_ref[i], 0, 0)),
            pl.BlockSpec((1, BS, W), lambda l, i, idx_ref: (l, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, BS, W),
                               lambda l, i, idx_ref: (l, idx_ref[i], 0, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(storage.shape, storage.dtype),
        input_output_aliases={1: 0},   # pool operand aliases the output
        interpret=interpret,
    )(idx, storage, buf.astype(storage.dtype))


def _layer_kernel(layer_ref, idx_ref, pool_ref, buf_ref, out_ref):
    out_ref[0, 0] = buf_ref[...]


def kv_scatter_layer_pallas(storage: jax.Array, buf: jax.Array,
                            idx: jax.Array, layer: jax.Array, *,
                            interpret: bool = True) -> jax.Array:
    """storage: (L, NB, BS, W); buf: (n*BS, W) stripe of one layer;
    idx: (n,) int32; layer: int32 scalar (traced). Returns the pool with
    blocks ``idx`` of ``layer`` overwritten (same buffer, aliased)."""
    *_, BS, W = storage.shape
    n = idx.shape[0]
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),      # aliased, never read
            pl.BlockSpec((BS, W), lambda i, l_ref, idx_ref: (i, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, BS, W),
            lambda i, l_ref, idx_ref: (l_ref[0], idx_ref[i], 0, 0)),
    )
    return pl.pallas_call(
        _layer_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(storage.shape, storage.dtype),
        input_output_aliases={2: 0},   # pool operand aliases the output
        interpret=interpret,
    )(layer, idx, storage, buf.astype(storage.dtype))
