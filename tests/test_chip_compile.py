"""Compile the serving path for a TPU v5e chip without a chip.

The TPU compiler is installed beside the CPU backend and compiles for a
described ``v5e:2x2`` topology, so these tests build the main path's
Pallas kernels (``interpret=False``) and the jitted prefill and fused
decode steps at granite-3-8b widths (32 q heads, 8 kv heads, head_dim
128, pool width 2048, block 16) in float32, and fail where the chip's
compiler would refuse them. Nothing runs: a pass says the programs
compile, not that they are right or fast.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and under
several test workers only the worker given this file may try.
"""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.flash_prefill import flash_prefill_pallas
from repro.kernels.kv_gather import kv_gather_pallas
from repro.kernels.kv_scatter import kv_scatter_pallas
from repro.kernels.paged_attention import paged_attention_pallas
from repro.models.caches import decode_slot_state
from repro.models.modeling import decode_step_jit
from repro.models.params import abstract_params
from repro.serving.engine import _jit_forward_prefill

NQ, NKV, HD, BS, NB = 32, 8, 128, 16, 256
W = 2 * NKV * HD                   # K ++ V pool width
SLOTS, TABLE = 8, 4
HBM = 16 * 2**30                   # one v5e chip


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 topology, with the persistent compilation
    cache off: a compile for a described chip cannot be read back here,
    and a half-written entry would warn in every later compile."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:     # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_case(name, sh):
    f32, i32 = jnp.float32, jnp.int32
    if name == "paged_attention":
        return (paged_attention_pallas,
                (_sds((SLOTS, NQ, HD), f32, sh), _sds((NB, BS, W), f32, sh),
                 _sds((SLOTS, TABLE), i32, sh), _sds((SLOTS,), i32, sh)))
    if name == "kv_gather":
        return (kv_gather_pallas,
                (_sds((1, NB, BS, W), f32, sh), _sds((4,), i32, sh)))
    if name == "kv_scatter":
        return (kv_scatter_pallas,
                (_sds((1, NB, BS, W), f32, sh), _sds((1, 4 * BS, W), f32, sh),
                 _sds((4,), i32, sh)))
    # flash prefill: heads flattened into the leading dim, 256 tokens
    return (flash_prefill_pallas,
            (_sds((NQ, 256, HD), f32, sh), _sds((NQ, 256, HD), f32, sh),
             _sds((NQ, 256, HD), f32, sh)))


@pytest.mark.parametrize("name", ["paged_attention", "kv_gather",
                                  "kv_scatter", "flash_prefill"])
def test_kernel_compiles_natively(one_chip, name):
    kernel, args = _kernel_case(name, one_chip)
    compiled = jax.jit(functools.partial(kernel, interpret=False)) \
        .lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_per_layer_scatter_updates_the_donated_pool_in_place(one_chip,
                                                              monkeypatch):
    """The KV hand-off's per-layer RecvScatter at the benchmark cell's
    decode pool (8 layers, 2400 blocks of 16, width 2048, float32): one
    program with the layer traced, the Pallas kernel inside, the donated
    pool aliased to the output and never copied whole."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    L, nb, n = 8, 2400, 32
    pool = (L, nb, BS, W)
    compiled = ops.kv_scatter_layer.lower(
        _sds(pool, jnp.float32, one_chip),
        _sds((n * BS, W), jnp.float32, one_chip),
        _sds((n,), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "input_output_alias={ {}: (0, {}" in text
    assert not re.search(rf"= f32\[{L},{nb},{BS},{W}\]\S* copy\(", text)
    pool_bytes = 4 * L * nb * BS * W
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 100


def _granite(layers):
    return get_config("granite-3-8b").replace(num_layers=layers)


def _params(cfg, sh):
    return jax.tree.map(lambda s: _sds(s.shape, s.dtype, sh),
                        abstract_params(cfg))


def test_fused_decode_step_compiles_with_pallas(one_chip, monkeypatch):
    """The fused decode step at full width carries the native paged-
    attention kernel. Off the TPU the step traces the jnp reference, so
    the test steers the kernel dispatch to the chip's branch."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = _granite(2)
    slots = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip),
                         jax.eval_shape(lambda: decode_slot_state(cfg,
                                                                  SLOTS)))
    vec = _sds((SLOTS,), jnp.int32, one_chip)
    compiled = decode_step_jit.lower(
        cfg, _params(cfg, one_chip),
        _sds((cfg.num_layers, NB, BS, W), jnp.float32, one_chip),
        _sds((SLOTS, TABLE), jnp.int32, one_chip), vec, vec,
        _sds((SLOTS,), jnp.bool_, one_chip), slots,
        block_size=BS).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < HBM


def test_forward_prefill_compiles(one_chip):
    """The shared jitted prefill, 4 requests in a 128-token bucket."""
    cfg = _granite(2)
    b, s = 4, 128
    compiled = _jit_forward_prefill.lower(
        cfg, _params(cfg, one_chip),
        {"tokens": _sds((b, s), jnp.int32, one_chip)},
        last_index=_sds((b,), jnp.int32, one_chip)).compile()
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < HBM
