"""Universal bucketed prefill (PR 5): EVERY config family right-pads
ragged batches to power-of-two length buckets through the one shared
jitted forward — exactly.

The pad-invariance contract under test:
  * bucketed output is token-identical to the exact-length path, per
    family, and the KV written for real positions matches it to float32
    rounding (the two run at different padded shapes, see
    ``parity_utils.CROSS_SHAPE_TOL``);
  * SSM/hybrid recurrent state (mamba2, jamba) matches the exact-length
    run to the same tolerance (zero-dt pads are state no-ops; conv tails
    are gathered at the valid boundary);
  * capacity-dispatch MoE (qwen2-moe, deepseek-moe) routes identically
    under padding — window-local capacity with a valid-count threshold
    and pads force-routed to the null slot — even when experts overflow
    and really drop tokens;
  * warm prefix-reuse admissions bucket BOTH the suffix and the prefix
    KV (traced q_offset), so retraces are O(bucket pairs), never
    O(distinct prefix lengths).
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hypothesis import given, settings, strategies as st

from conftest import ALL_ARCHS, reduced_params
from parity_utils import make_frames as _frames, make_prompts as _prompts, \
    outputs_close as _outputs_close, serve_sequential, prefill_node, \
    CROSS_SHAPE_TOL
from repro.kernels import ref
from repro.serving import trace
from repro.serving.cluster import ServeRequest
from repro.serving.engine import PrefillEngine, prefill_compile_count

RAGGED_LENS = (5, 13, 8)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_bucketed_matches_exact_per_family(arch):
    """Ragged + warm-prefix workload per family: bucketed == exact
    (tokens exactly; KV and mamba recurrent state to float32 rounding;
    MoE routing), with
    the compile count pinned to the bucket set, not the length set."""
    cfg, params = reduced_params(arch)
    rng = np.random.default_rng(9)
    prompts = _prompts(cfg, rng, RAGGED_LENS)
    frames = _frames(cfg, rng, len(prompts))
    exact = PrefillEngine(cfg, params, bucket_prefill=False)
    bucketed = PrefillEngine(cfg, params, bucket_prefill=True)
    assert not hasattr(bucketed, "supports_bucketing")  # gate DELETED
    o_e = exact.run(prompts, frames=frames)
    c0 = prefill_compile_count()
    o_b = bucketed.run(prompts, frames=frames)
    bucket_compiles = prefill_compile_count() - c0
    for a, b in zip(o_e, o_b):
        _outputs_close(a, b)
    # accounting stays exact; padding is ledgered separately
    assert exact.compute_tokens == bucketed.compute_tokens \
        == sum(RAGGED_LENS)
    assert bucketed.padded_tokens > exact.padded_tokens
    assert bucket_compiles <= 1          # one (batch, bucket) shape
    # a SECOND ragged wave with all-new lengths in the same bucket must
    # not retrace (O(num_buckets), not O(distinct lengths))
    c1 = prefill_compile_count()
    wave2 = _prompts(cfg, rng, (7, 12, 6))
    frames2 = _frames(cfg, rng, 3)
    o_w = bucketed.run(wave2, frames=frames2)
    assert prefill_compile_count() == c1
    # the build counter: the same wave again builds no program at all
    b1 = trace.build_count()
    bucketed.run(wave2, frames=frames2)
    assert trace.build_count() == b1
    ref_w = exact.run(wave2, frames=frames2)
    for a, b in zip(ref_w, o_w):
        assert a.first_token == b.first_token
    # warm prefix-reuse leg (attention stacks): suffix-only prefill with
    # a BUCKETED prefix must match the cold run and reuse the program.
    # SSM/hybrid families need a boundary state snapshot for warm runs —
    # their warm parity (incl. bucketing) is pinned in
    # tests/test_state_snapshot_reuse.py
    if not bucketed.supports_prefix_reuse or bucketed.requires_state_restore:
        return
    plen = 16                            # capacity-window aligned
    long = _prompts(cfg, rng, (plen + 5,))[0]
    fr = _frames(cfg, rng, 1)
    cold, = bucketed.run([long], frames=fr)
    pkv = jnp.concatenate([cold.k[:, :plen], cold.v[:, :plen]], axis=-1)
    warm = bucketed.run_suffix(long[plen:], pkv,
                               frames=fr[0] if fr else None)
    assert warm.first_token == cold.first_token
    np.testing.assert_allclose(np.asarray(warm.k), np.asarray(cold.k),
                               **CROSS_SHAPE_TOL)
    assert warm.prompt_len == cold.prompt_len


def test_suffix_retraces_bounded_by_bucket_pairs():
    """Distinct prefix lengths inside one prefix bucket must share one
    compiled suffix program: the prefix KV is padded to the bucket and
    the real length is a traced operand, so retraces scale with
    (prefix bucket, suffix bucket) pairs only."""
    cfg, params = reduced_params("granite-3-8b")
    rng = np.random.default_rng(13)
    pe = PrefillEngine(cfg, params, bucket_prefill=True)
    long = _prompts(cfg, rng, (40,))[0]
    cold, = pe.run([long])
    cases = [(17, 5), (20, 9), (25, 3), (31, 6),        # prefix bucket 32
             (16, 5), (9, 4)]                           # prefix bucket 16
    pairs = {(pe._bucket_len(p), pe._bucket_len(s)) for p, s in cases}
    base = prefill_compile_count()
    firsts = {}
    for plen, slen in cases:
        pkv = jnp.concatenate([cold.k[:, :plen], cold.v[:, :plen]],
                              axis=-1)
        warm = pe.run_suffix(long[plen:plen + slen], pkv)
        firsts[(plen, slen)] = warm.first_token
    delta = prefill_compile_count() - base
    assert delta <= len(pairs) < len(cases)
    # and the warm outputs are right: spot-check against cold prefills
    for plen, slen in cases[:2]:
        want, = PrefillEngine(cfg, params,
                              bucket_prefill=False).run([long[:plen + slen]])
        assert firsts[(plen, slen)] == want.first_token, (plen, slen)


def test_capacity_moe_drops_are_pad_invariant():
    """Force real capacity overflow (tiny capacity_factor) and check a
    padded row still produces the exact-length outputs: the keep
    threshold comes from the VALID token count and pads take no slots."""
    cfg, params = reduced_params("qwen2-moe-a2.7b")
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                              capacity_factor=0.25))
    from repro.models.modeling import forward_prefill
    rng = np.random.default_rng(2)
    ln = 11
    toks = rng.integers(0, cfg.vocab_size, ln)
    li = jnp.asarray([ln - 1])
    f_e, c_e = forward_prefill(cfg, params,
                               {"tokens": jnp.asarray(toks[None],
                                                      jnp.int32)},
                               last_index=li)
    pad = np.zeros(16, np.int64)
    pad[:ln] = toks
    f_p, c_p = forward_prefill(cfg, params,
                               {"tokens": jnp.asarray(pad[None],
                                                      jnp.int32)},
                               last_index=li)
    assert int(f_e[0]) == int(f_p[0])
    for sub, leaves in c_e["layers"].items():
        for name, a in leaves.items():
            b = np.asarray(c_p["layers"][sub][name])[:, :, :ln] \
                if name in ("k", "v") else np.asarray(c_p["layers"][sub][name])
            np.testing.assert_allclose(np.asarray(a), b,
                                       err_msg=f"{sub}/{name}",
                                       **CROSS_SHAPE_TOL)


def test_capacity_moe_warm_prefix_matches_cold_serving():
    """The lifted prefix-index gate, end to end: capacity-dispatch MoE
    served warm (window-aligned prefix hits, suffix-only prefill) must
    be token-identical to cold serving."""
    cfg, params = reduced_params("qwen2-moe-a2.7b")
    assert cfg.moe.dispatch == "capacity"
    rng = np.random.default_rng(3)
    prefix = list(map(int, rng.integers(0, cfg.vocab_size,
                                        cfg.moe.capacity_window)))
    prompts = [prefix + list(map(int, rng.integers(0, cfg.vocab_size, 5)))
               for _ in range(3)]

    cold, _ = serve_sequential(cfg, params, prompts, prefix_cache=False)
    warm, fe = serve_sequential(cfg, params, prompts, prefix_cache=True)
    assert warm == cold
    g = fe.groups["default"]
    node = prefill_node(fe)
    assert node.prefix_cache and node.prefix_align \
        == cfg.moe.capacity_window
    assert node.pool.hits == len(prompts) - 1
    assert node.engine.reused_tokens == \
        cfg.moe.capacity_window * (len(prompts) - 1)
    # compile-stall telemetry rides on the group ledger
    ts = g.transfer_stats()
    assert ts["prefill_compile_count"] >= 1.0
    assert ts["prefill_batches"] == float(node.engine.prefill_batches)
    # builds inside prefill batches (counted while tracing is on): a warm
    # request of a shape already served builds none
    again = ServeRequest(rid=len(prompts), max_new_tokens=3, tokens=prefix
                         + list(map(int, rng.integers(0, cfg.vocab_size,
                                                      5))))
    trace.enable()
    try:
        fe.run([again])
    finally:
        trace.enable(False)
    assert again.done
    assert g.transfer_stats()["prefill_builds"] == ts["prefill_builds"]
    # pad waste only exists on the bucketed default (an engine built
    # with bucket_prefill=False pads nothing)
    assert 0.0 <= ts["prefill_pad_waste"] < 1.0
    if node.engine.bucket_prefill:
        assert ts["prefill_pad_waste"] > 0.0


def test_flash_prefill_bucketed_prefix_and_query_mask():
    """Kernel-level contract: a right-padded prefix region (prefix_pad >
    q_offset) and padded query rows (q_valid) must reproduce the
    exact-length oracle on valid rows, with padded queries emitting
    exactly zero."""
    from repro.kernels.flash_prefill import flash_prefill_pallas
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.normal(size=(2, 128, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 256, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 256, 32)), jnp.float32)
    got = flash_prefill_pallas(q, k, v, q_tile=64, kv_tile=64,
                               interpret=True, q_offset=70,
                               prefix_pad=128, q_valid=100)
    want = ref.flash_prefill(q, k, v, q_offset=70, prefix_pad=128,
                             q_valid=100)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # valid rows equal an exact-length (no prefix padding) run
    ke = jnp.concatenate([k[:, :70], k[:, 128:]], axis=1)
    ve = jnp.concatenate([v[:, :70], v[:, 128:]], axis=1)
    exact = ref.flash_prefill(q, ke, ve, q_offset=70)
    np.testing.assert_allclose(np.asarray(got)[:, :100],
                               np.asarray(exact)[:, :100],
                               rtol=2e-5, atol=2e-5)
    assert np.all(np.asarray(got)[:, 100:] == 0.0)
    assert np.all(np.asarray(want)[:, 100:] == 0.0)


@settings(max_examples=8, deadline=None)
@given(lens=st.lists(st.integers(min_value=1, max_value=15),
                     min_size=1, max_size=4))
def test_padding_never_changes_outputs_or_compute(lens):
    """Property: for ANY ragged batch, bucketing changes neither the
    emitted tokens nor the exact compute_tokens ledger — padding exists
    only in padded_tokens."""
    cfg, params = reduced_params("granite-3-8b")
    rng = np.random.default_rng(sum(lens) + len(lens))
    prompts = _prompts(cfg, rng, lens)
    exact = PrefillEngine(cfg, params, bucket_prefill=False)
    bucketed = PrefillEngine(cfg, params, bucket_prefill=True)
    o_e = exact.run(prompts)
    o_b = bucketed.run(prompts)
    assert [o.first_token for o in o_e] == [o.first_token for o in o_b]
    assert exact.compute_tokens == bucketed.compute_tokens == sum(lens)
    assert exact.padded_tokens <= bucketed.padded_tokens
