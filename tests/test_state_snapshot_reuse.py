"""Recurrent-state snapshot reuse (PR 6): SSM/hybrid prefix hits.

The contract under test, per family (pure SSM: mamba2; hybrid
attn+SSM+capacity-MoE: jamba; hybrid with dropless MoE): a warm
suffix-only prefill restored from a boundary snapshot must be

  * token-identical to the cold full prefill (first token AND the
    decode stream it seeds, fused and eager);
  * bit-identical in recurrent state at decode hand-off — conv tails
    (x/B/C windows) and the SSD inter-chunk state, every layer;
  * bit-identical in the KV it stitches for attention layers and in the
    snapshots it RE-EMITS at later boundaries (chained reuse);

with hits landing only on snapshot-stride boundaries (non-boundary cuts
degrade to the nearest boundary DOWN, never a COW tail), and warm
admissions reusing the compiled suffix program across waves.
"""
import dataclasses
import math

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reduced_params
from parity_utils import BS, admit, assert_state_equal, prefill_node, \
    serve_sequential
from repro.serving import trace
from repro.serving.engine import DecodeEngine, PrefillEngine, \
    prefill_compile_count
from repro.serving.kvcache import PagedKVPool

# (arch, MoE dispatch override): pure SSM / hybrid + capacity MoE /
# hybrid + dropless sorted MoE — param shapes identical across dispatch
VARIANTS = [
    ("mamba2-2.7b", None),
    ("jamba-1.5-large-398b", None),
    ("jamba-1.5-large-398b", "sorted"),
]
IDS = ["mamba2", "jamba-capacity", "jamba-sorted"]

def _family(arch, dispatch):
    cfg, params = reduced_params(arch)
    if dispatch is not None and cfg.moe is not None:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                  dispatch=dispatch))
    return cfg, params


def _pkv(out, plen):
    if out.k is None:
        return None
    return jnp.concatenate([out.k[:, :plen], out.v[:, :plen]], axis=-1)


def _prompt(cfg, rng, n):
    return list(map(int, rng.integers(0, cfg.vocab_size, n)))


@pytest.mark.parametrize("arch,dispatch", VARIANTS, ids=IDS)
def test_warm_restore_is_bitwise_at_every_boundary(arch, dispatch):
    """Engine-level pin: restore from EACH emitted boundary; outputs
    token-identical, stitched KV + full recurrent state + re-emitted
    later snapshots bitwise. The short-suffix leg (suffix < conv k-1)
    forces the conv window to straddle the restore boundary."""
    cfg, params = reduced_params(arch) if dispatch is None \
        else _family(arch, dispatch)
    pe = PrefillEngine(cfg, params)
    assert pe.supports_prefix_reuse and pe.requires_state_restore
    stride = pe.prefix_align
    assert stride % cfg.ssm_cfg.chunk == 0
    rng = np.random.default_rng(17)
    for suffix_len in (7, 2):            # 2 < conv_width-1 for d_conv 4
        prompt = _prompt(cfg, rng, 2 * stride + suffix_len)
        cold, = pe.run([prompt], snap_stride=stride)
        assert set(cold.snapshots) == {stride, 2 * stride}
        for boundary in (stride, 2 * stride):
            warm = pe.run_suffix(
                prompt[boundary:], _pkv(cold, boundary),
                state=cold.snapshots[boundary], prefix_len=boundary,
                snap_stride=stride)
            ctx = (arch, dispatch, suffix_len, boundary)
            assert warm.first_token == cold.first_token, ctx
            assert warm.prompt_len == cold.prompt_len, ctx
            if cold.k is not None:
                assert np.array_equal(np.asarray(cold.k),
                                      np.asarray(warm.k)), ctx
                assert np.array_equal(np.asarray(cold.v),
                                      np.asarray(warm.v)), ctx
            assert_state_equal(cold.mamba_state, warm.mamba_state,
                               ctx=str(ctx))
            # boundaries re-emitted over the suffix chain bitwise
            for t, snap in (warm.snapshots or {}).items():
                assert_state_equal(cold.snapshots[t], snap,
                                   ctx=f"{ctx} snap@{t}")
            assert pe.state_restores > 0


@pytest.mark.parametrize("arch,dispatch", VARIANTS, ids=IDS)
def test_decode_handoff_from_restored_state(arch, dispatch):
    """The restored-and-advanced warm state admits into decode (fused
    AND eager) producing the cold stream exactly."""
    cfg, params = _family(arch, dispatch)
    pe = PrefillEngine(cfg, params)
    stride = pe.prefix_align
    rng = np.random.default_rng(23)
    prompt = _prompt(cfg, rng, stride + 5)
    cold, = pe.run([prompt], snap_stride=stride)
    warm = pe.run_suffix(prompt[stride:], _pkv(cold, stride),
                         state=cold.snapshots[stride], prefix_len=stride,
                         snap_stride=stride)
    for fused in (False, True):
        streams = []
        for out in (cold, warm):
            pool = PagedKVPool(cfg, num_blocks=48, block_size=BS)
            de = DecodeEngine(cfg, params, pool, max_slots=2, fused=fused)
            admit(pool, de, 0, out)
            gen = [out.first_token]
            for _ in range(4):
                gen.append(de.step()[0])
            streams.append(gen)
        assert streams[0] == streams[1], (arch, dispatch, fused)


@pytest.mark.parametrize("arch,dispatch", VARIANTS, ids=IDS)
def test_warm_serving_matches_cold_through_frontend(arch, dispatch):
    """End to end through ClusterFrontend: SSM-family warm serving is
    token-identical to cold, the snapshot index records the hits, and
    the transfer scheduler ships the restored state segment."""
    cfg, params = _family(arch, dispatch)
    rng = np.random.default_rng(29)
    prefix = _prompt(cfg, rng, 35)
    prompts = [prefix + _prompt(cfg, rng, 5) for _ in range(3)]
    cold, _ = serve_sequential(cfg, params, prompts, prefix_cache=False,
                               max_new=2)
    warm, fe = serve_sequential(cfg, params, prompts, prefix_cache=True,
                                max_new=2)
    assert warm == cold
    node = prefill_node(fe)
    stride = node.snap_stride
    assert stride and stride % BS == 0
    reused = 35 - 35 % stride            # non-boundary cut degrades DOWN
    ps = fe.groups["default"].prefix_stats()
    assert ps["snap_hits"] == len(prompts) - 1
    assert ps["snap_stores"] >= 1 and ps["snap_bytes"] > 0
    assert ps["state_restores"] == len(prompts) - 1
    assert node.engine.reused_tokens == reused * (len(prompts) - 1)
    assert node.pool.invariant_ok()
    # every SSM admission carries a trailing state segment; the warm
    # ones ship the RESTORED state rather than a recomputed one
    ts = fe.groups["default"].transfer_stats()
    assert ts["state_segments"] >= len(prompts)
    assert ts["state_payload_bytes"] > 0


def test_non_boundary_cut_degrades_to_snapshot_boundary():
    """Pool-level floor semantics: a require_state acquire rounds an
    aligned trie match DOWN to the nearest boundary that still HOLDS a
    snapshot — stale boundaries (evicted snapshot) are skipped, and a
    prefix with no surviving boundary is a clean miss (counted)."""
    cfg, _ = reduced_params("granite-3-8b")
    pool = PagedKVPool(cfg, num_blocks=64, block_size=4,
                       enable_prefix_cache=True)
    toks = list(range(70))
    snap = lambda t: {"state": np.full((2, 2), float(t), np.float32)}
    pool.alloc(0, len(toks))
    pool.insert_prefix(0, toks, states={32: snap(32), 64: snap(64)})
    assert pool.snap_stores == 2
    # 70-token prompt, align 32: target 64, boundary 64 holds a snapshot
    got = pool.acquire_prefix(1, toks + [99], align=32, require_state=True)
    assert got == 64 and pool.snap_hits == 1
    assert pool.snapshot_for(1, got)["state"][0, 0] == 64.0
    # drop the 64-boundary snapshot (simulates its block being evicted):
    # the same acquire now floors to 32
    blk64 = pool.owned(1)[64 // 4 - 1]
    pool._snaps.pop(blk64)
    pool.release(1)
    got = pool.acquire_prefix(2, toks + [99], align=32, require_state=True)
    assert got == 32 and pool.snapshot_for(2, got)["state"][0, 0] == 32.0
    pool.release(2)
    # no surviving boundary at all -> clean miss, no refs, counted
    pool._snaps.clear()
    misses = pool.snap_misses
    got = pool.acquire_prefix(3, toks + [99], align=32, require_state=True)
    assert got == 0 and pool.owned(3) == []
    assert pool.snap_misses == misses + 1
    assert pool.invariant_ok()


def test_second_wave_reuses_compiled_suffix_program():
    """Zero-retrace guard: a second wave of warm restores with the same
    (prefix len, suffix bucket, stride) shapes — different tokens, a
    different boundary state — must not compile anything new."""
    cfg, params = reduced_params("jamba-1.5-large-398b")
    pe = PrefillEngine(cfg, params)
    stride = pe.prefix_align
    rng = np.random.default_rng(31)
    p1 = _prompt(cfg, rng, stride + 6)
    p2 = _prompt(cfg, rng, stride + 6)
    cold1, = pe.run([p1], snap_stride=stride)
    cold2, = pe.run([p2], snap_stride=stride)
    pe.run_suffix(p1[stride:], _pkv(cold1, stride),
                  state=cold1.snapshots[stride], prefix_len=stride,
                  snap_stride=stride)
    c0 = prefill_compile_count()
    b0 = trace.build_count()
    warm2 = pe.run_suffix(p2[stride:], _pkv(cold2, stride),
                          state=cold2.snapshots[stride], prefix_len=stride,
                          snap_stride=stride)
    assert prefill_compile_count() == c0          # no retrace
    assert trace.build_count() == b0              # no build at all
    assert warm2.first_token == cold2.first_token
    assert_state_equal(cold2.mamba_state, warm2.mamba_state)


def test_snapshot_stride_is_lcm_of_block_chunk_and_window():
    """The serving node's stride must divide evenly into pool blocks,
    SSD chunks, and (when present) capacity windows — the invariant
    that makes require_state acquires land on whole-block, chunk-exact,
    window-exact boundaries (so restores are bitwise and never COW)."""
    for arch, dispatch in VARIANTS:
        cfg, params = _family(arch, dispatch)
        _, fe = serve_sequential(cfg, params, [[1, 2, 3]],
                                 prefix_cache=True, max_new=1)
        node = prefill_node(fe)
        assert node.needs_state
        want = math.lcm(node.engine.prefix_align, BS)
        assert node.snap_stride == node.prefix_align == want
        assert node.snap_stride % cfg.ssm_cfg.chunk == 0
        assert node.snap_stride % BS == 0
        if cfg.moe is not None and cfg.moe.dispatch == "capacity":
            assert node.snap_stride % cfg.moe.capacity_window == 0


def test_reuse_gate_follows_prefill_geometry():
    """The snapshot-reuse gate is a function of the prefill geometry:
    bucketed (the default — the env hatch is retired) => on (bitwise
    contract holds), exact-length via the ``bucket_prefill=False``
    constructor arg => off (no geometry control — a tiny suffix program
    wobbles the SSD state by ulps, and hybrids cannot pad without
    breaking the attention key geometry)."""
    cfg, params = reduced_params("mamba2-2.7b")
    pe = PrefillEngine(cfg, params)
    assert pe.bucket_prefill
    assert pe.supports_prefix_reuse
    assert pe.requires_state_restore
    for arch in ("mamba2-2.7b", "jamba-1.5-large-398b"):
        c, p = reduced_params(arch)
        assert not PrefillEngine(c, p,
                                 bucket_prefill=False).supports_prefix_reuse
        assert PrefillEngine(c, p,
                             bucket_prefill=True).supports_prefix_reuse
    # attention-only families reuse prefixes in EITHER geometry
    cg, pg = reduced_params("granite-3-8b")
    assert PrefillEngine(cg, pg, bucket_prefill=False).supports_prefix_reuse
