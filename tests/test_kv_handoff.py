"""The per-layer RecvScatter of the KV hand-off
(``PagedKVPool.scatter_layer`` -> ``ops.kv_scatter_layer``): one jitted
program per block count with the pool donated. It lands a layer's stripe
bitwise as ``storage.at[layer, idx].set``, leaves every other block and
layer as it was, and a later layer's scatter of the same block count
reuses the build."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.kv_scatter import kv_scatter_layer_pallas
from repro.serving import trace
from repro.serving.kvcache import PagedKVPool

NB, BS = 24, 4


def _pool(seed=0):
    pool = PagedKVPool(get_config("granite-3-8b").reduced(), num_blocks=NB,
                       block_size=BS)
    rng = np.random.default_rng(seed)
    pool.storage = jnp.asarray(rng.normal(size=pool.storage.shape),
                               pool.dtype)
    return pool, rng


def _stripe(pool, rng, n):
    return jnp.asarray(rng.normal(size=(n * BS, pool.width)), pool.dtype)


@pytest.mark.parametrize("n,layer", [(1, 0), (3, 1), (5, -1), (NB, 0)])
def test_scatter_layer_is_bitwise_the_ref(n, layer):
    pool, rng = _pool()
    layer %= pool.storage.shape[0]
    before = np.array(pool.storage)
    blocks = rng.permutation(NB)[:n].tolist()
    buf = _stripe(pool, rng, n)
    want = np.asarray(jnp.asarray(before).at[layer, jnp.asarray(blocks)]
                      .set(buf.reshape(n, BS, pool.width)))
    old = pool.storage
    pool.scatter_layer(buf, blocks, layer)
    assert old.is_deleted()                  # donated, updated in place
    got = np.asarray(pool.storage)
    assert got.tobytes() == want.tobytes()
    # untouched blocks of the layer and every other layer keep their bytes
    keep = np.ones(got.shape[:2], bool)
    keep[layer, blocks] = False
    assert got[keep].tobytes() == before[keep].tobytes()
    np.testing.assert_array_equal(
        got[layer, blocks], np.asarray(buf).reshape(n, BS, pool.width))


@pytest.mark.parametrize("n", [2, 7])
def test_same_block_count_other_layer_adds_no_build(n):
    pool, rng = _pool(seed=n)
    layers = pool.storage.shape[0]
    assert layers >= 2
    pool.scatter_layer(_stripe(pool, rng, n), list(range(n)), 0)
    built = trace.build_count()
    for layer in range(1, layers):
        pool.scatter_layer(_stripe(pool, rng, n),
                           rng.permutation(NB)[:n].tolist(), layer)
    assert trace.build_count() == built


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,layer", [(1, 2), (4, 0), (9, 1)])
def test_layer_kernel_matches_wrapper(dtype, n, layer):
    """The TPU body of the wrapper, run by the grid interpreter, against
    the off-TPU body on the same operands."""
    rng = np.random.default_rng(n + layer)
    storage = jnp.asarray(rng.normal(size=(3, NB, BS, 64)), dtype)
    idx = jnp.asarray(rng.permutation(NB)[:n], jnp.int32)
    buf = jnp.asarray(rng.normal(size=(n * BS, 64)), dtype)
    got = kv_scatter_layer_pallas(storage, buf, idx, jnp.int32(layer),
                                  interpret=True)
    want = ops.kv_scatter_layer(jnp.copy(storage), buf, idx, layer)
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
