"""Fused jitted decode step + bucketed prefill (the hot-loop rework).

The fused path must be TOKEN-IDENTICAL to the eager per-layer loop for
every family — including slot churn (admit/evict mid-stream) and warm
prefix-reuse admissions — while doing exactly one pool-storage swap per
step with the old buffer donated, and retracing only when a shape
bucket changes.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import reduced_params
from parity_utils import BS, CROSS_SHAPE_TOL, admit as _admit, \
    decode_setup as _setup
from repro.models.modeling import decode_step_cache_size, forward_decode, \
    forward_prefill
from repro.serving.engine import DecodeEngine, PrefillEngine, \
    prefill_compile_count
from repro.serving.kvcache import PagedKVPool

FAMILIES = ["granite-3-8b", "qwen2-moe-a2.7b", "mamba2-2.7b",
            "jamba-1.5-large-398b", "pixtral-12b", "whisper-base"]


def _churn_run(cfg, params, outs, *, fused, num_blocks=48):
    """Admit 0..1, decode, admit 2 mid-stream, evict 0, keep going —
    returns {rid: generated tokens} under a fixed churn schedule."""
    pool = PagedKVPool(cfg, num_blocks=num_blocks, block_size=BS)
    de = DecodeEngine(cfg, params, pool, max_slots=3, fused=fused)
    assert de.fused is fused
    gen = {rid: [out.first_token] for rid, out in enumerate(outs)}

    def steps(n):
        for _ in range(n):
            for slot, tok in de.step().items():
                gen[de.rid[slot]].append(tok)

    slot0 = _admit(pool, de, 0, outs[0])
    _admit(pool, de, 1, outs[1])
    steps(3)
    _admit(pool, de, 2, outs[2])          # admitted mid-flight
    steps(2)
    de.evict(slot0)                       # rid 0 leaves, others continue
    pool.release(0)
    steps(3)
    return gen


@pytest.mark.parametrize("arch", FAMILIES)
def test_fused_matches_eager_with_slot_churn(arch):
    cfg, params, prompts, frames = _setup(arch)
    pe = PrefillEngine(cfg, params)
    outs = pe.run(prompts, frames=frames)
    eager = _churn_run(cfg, params, outs, fused=False)
    fused = _churn_run(cfg, params, outs, fused=True)
    assert fused == eager, arch


def test_fused_matches_lockstep_oracle():
    """Anchor fused-vs-eager agreement to ground truth on one family."""
    cfg, params, prompts, _ = _setup("granite-3-8b")
    pe = PrefillEngine(cfg, params)
    outs = pe.run(prompts)
    pool = PagedKVPool(cfg, num_blocks=48, block_size=BS)
    de = DecodeEngine(cfg, params, pool, max_slots=4, fused=True)
    gen = {}
    for rid, out in enumerate(outs):
        _admit(pool, de, rid, out)
        gen[rid] = [out.first_token]
    for _ in range(4):
        for slot, tok in de.step().items():
            gen[de.rid[slot]].append(tok)
    for rid, toks in enumerate(prompts):
        batch = {"tokens": jnp.asarray([toks], jnp.int32)}
        first, cache = forward_prefill(cfg, params, batch)

        def pad(path, x):
            nm = path[-1].key if hasattr(path[-1], "key") else ""
            if nm in ("k", "v") and x.ndim == 4:
                return jnp.pad(x, ((0, 0), (0, 0), (0, 6), (0, 0)))
            return x
        cache = {"layers": jax.tree_util.tree_map_with_path(
            pad, cache["layers"]), "pos": cache["pos"]}
        seq, tok = [int(first[0])], first
        for _ in range(4):
            tok, cache = forward_decode(cfg, params, cache, tok)
            seq.append(int(tok[0]))
        assert gen[rid] == seq, rid


def test_fused_matches_eager_on_warm_prefix_admission():
    """A suffix-only (prefix-reuse) prefill feeds both decode paths the
    same stitched KV; the generated streams must agree."""
    cfg, params, _, _ = _setup("granite-3-8b")
    rng = np.random.default_rng(11)
    prefix = list(map(int, rng.integers(0, cfg.vocab_size, 8)))
    suffix = list(map(int, rng.integers(0, cfg.vocab_size, 5)))
    pe = PrefillEngine(cfg, params)
    cold, = pe.run([prefix + suffix])
    plen = 8
    prefix_kv = jnp.concatenate(
        [cold.k[:, :plen], cold.v[:, :plen]], axis=-1)
    warm = pe.run_suffix(suffix, prefix_kv)
    assert warm.first_token == cold.first_token
    gens = {}
    for fused in (False, True):
        pool = PagedKVPool(cfg, num_blocks=48, block_size=BS)
        de = DecodeEngine(cfg, params, pool, max_slots=2, fused=fused)
        _admit(pool, de, 0, warm)
        gen = [warm.first_token]
        for _ in range(5):
            gen.append(de.step()[0])
        gens[fused] = gen
    assert gens[True] == gens[False]


def test_fused_step_donates_pool_and_swaps_once():
    """The donation/aliasing contract: the fused step consumes the old
    pool buffer (donated into the jitted program, so XLA updates it in
    place) and the engine swaps storage exactly ONCE per iteration; the
    eager loop pays one swap — a full pool copy — per attention layer
    per step."""
    cfg, params, prompts, _ = _setup("granite-3-8b")
    pe = PrefillEngine(cfg, params)
    outs = pe.run(prompts[:2])
    for fused in (True, False):
        pool = PagedKVPool(cfg, num_blocks=48, block_size=BS)
        de = DecodeEngine(cfg, params, pool, max_slots=2, fused=fused)
        for rid, out in enumerate(outs):
            _admit(pool, de, rid, out)
        base = pool.storage_writes
        old = pool.storage
        de.step()
        writes = pool.storage_writes - base
        if fused:
            assert writes == 1
            assert old.is_deleted()          # donated, not copied
        else:
            assert writes == len(pe.layer_fractions())  # per attn layer
            assert not old.is_deleted()


def test_decode_retraces_bounded_by_table_bucket():
    """Steady-state churn inside one block-table bucket must reuse a
    single compiled fused step; crossing the bucket adds exactly one."""
    cfg, params, prompts, _ = _setup("granite-3-8b")
    pe = PrefillEngine(cfg, params)
    outs = pe.run(prompts)
    # unique pool geometry -> unique jit cache keys for this test
    pool = PagedKVPool(cfg, num_blocks=40, block_size=BS)
    de = DecodeEngine(cfg, params, pool, max_slots=3, fused=True)
    base = decode_step_cache_size()
    slot = _admit(pool, de, 0, outs[0])
    de.step()
    de.evict(slot)
    pool.release(0)
    _admit(pool, de, 1, outs[1])          # same bucket: no retrace
    de.step()
    de.step()
    assert decode_step_cache_size() - base == 1
    # a request spanning more blocks bumps the pow2 table bucket: +1
    long_prompt = list(np.random.default_rng(0).integers(
        0, cfg.vocab_size, 30))
    out_long, = pe.run([long_prompt])
    _admit(pool, de, 2, out_long, room=40)
    de.step()
    assert decode_step_cache_size() - base == 2


def test_prefill_retraces_bounded_by_buckets():
    """Ragged prompt lengths must compile O(num_buckets) prefill
    programs, not O(distinct lengths)."""
    cfg, params, _, _ = _setup("granite-3-8b")
    pe = PrefillEngine(cfg, params)
    assert pe.bucket_prefill
    rng = np.random.default_rng(2)
    lengths = list(range(5, 29))          # 24 distinct ragged lengths
    rng.shuffle(lengths)
    base = prefill_compile_count()
    shapes = set()
    for i in range(0, len(lengths), 4):
        batch = [list(rng.integers(0, cfg.vocab_size, n))
                 for n in lengths[i:i + 4]]
        groups = {}
        for t in batch:
            groups.setdefault(pe._bucket_len(len(t)), []).append(t)
        shapes |= {(len(g), b) for b, g in groups.items()}
        pe.run(batch)
    delta = prefill_compile_count() - base
    assert delta <= len(shapes) <= 8      # buckets {16, 32} x batch sizes
    assert delta < len(set(lengths))      # strictly beats per-length


def test_bucketed_prefill_is_exact():
    """Bucket padding must be inert: identical tokens vs exact-length
    prefill, and the KV written for real positions equal to float32
    rounding (the two run at different padded shapes)."""
    cfg, params, prompts, _ = _setup("granite-3-8b", n_prompts=4)
    exact = PrefillEngine(cfg, params, bucket_prefill=False)
    bucketed = PrefillEngine(cfg, params, bucket_prefill=True)
    o_e = exact.run(prompts)
    o_b = bucketed.run(prompts)
    for a, b in zip(o_e, o_b):
        assert a.first_token == b.first_token
        np.testing.assert_allclose(np.asarray(a.k), np.asarray(b.k),
                                   **CROSS_SHAPE_TOL)
        np.testing.assert_allclose(np.asarray(a.v), np.asarray(b.v),
                                   **CROSS_SHAPE_TOL)
    # the accounting stays exact: padding is tracked separately
    total = sum(len(p) for p in prompts)
    assert exact.compute_tokens == bucketed.compute_tokens == total
    assert exact.padded_tokens < bucketed.padded_tokens


def test_bucketing_universal_and_hatch_retired(monkeypatch):
    """Every family takes the bucketed path by default (the forward is
    pad-invariant by contract — there is no supports_bucketing gate
    anymore). The one-release REPRO_PREFILL=exact env hatch is retired:
    the environment is ignored and exact-length grouping is reachable
    only through the explicit ``bucket_prefill=False`` constructor
    arg."""
    for arch in ("mamba2-2.7b", "jamba-1.5-large-398b", "qwen2-moe-a2.7b",
                 "granite-3-8b"):
        cfg, params = reduced_params(arch)
        assert PrefillEngine(cfg, params).bucket_prefill, arch
        assert not hasattr(PrefillEngine(cfg, params), "supports_bucketing")
    cfg, params = reduced_params("granite-3-8b")
    assert not PrefillEngine(cfg, params, bucket_prefill=False).bucket_prefill
    # the retired env spelling is inert
    monkeypatch.setenv("REPRO_PREFILL", "exact")
    assert PrefillEngine(cfg, params).bucket_prefill
