"""Shared warm/cold parity scaffolding for the serving test suites.

One place for the helpers that every parity suite re-derived locally
(test_prefix_reuse, test_bucketed_prefill, test_fused_decode,
test_state_snapshot_reuse): prompt/frame generation, the sequential
1P:1D frontend driver, the PrefillOutput comparators, and the
decode-admission shim. Keeping them here means the parity CONTRACT is
stated once — a suite that needs a stricter or looser comparison says
so explicitly instead of forking a helper.
"""
import dataclasses

import numpy as np

from conftest import reduced_params
from repro.serving.cluster import ServeRequest
from repro.serving.frontend import ClusterFrontend

# pool geometry shared by the serving parity suites: small blocks force
# multi-block prefixes (and COW tails) even at reduced prompt lengths
POOL_KW = {"block_size": 4, "num_blocks": 96}
BS = POOL_KW["block_size"]

def make_prompts(cfg, rng, lens):
    return [list(map(int, rng.integers(0, cfg.vocab_size, int(n))))
            for n in lens]


def make_frames(cfg, rng, n):
    """Encoder frames for enc-dec configs, else None."""
    if not cfg.is_encoder_decoder:
        return None
    return [np.asarray(rng.normal(size=(cfg.encoder_seq, cfg.d_model)) * 0.1,
                       np.float32) for _ in range(n)]


def family_setup(arch, rng, *, sorted_moe=True):
    """(cfg, params, frames) for one family.

    ``sorted_moe`` swaps capacity dispatch for the dropless sorted
    dispatch (identical param shapes): capacity drops are a function of
    the window population, so suites that reuse prefixes at NON-window
    boundaries need sorted dispatch for exact parity. Window-aligned
    suites (snapshot reuse aligns to lcm(window, chunk, block)) keep
    capacity dispatch and still match bitwise.
    """
    cfg, params = reduced_params(arch)
    if sorted_moe and cfg.moe is not None:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                  dispatch="sorted"))
    frames = None
    if cfg.is_encoder_decoder:
        frames = np.asarray(
            rng.normal(size=(cfg.encoder_seq, cfg.d_model)) * 0.1,
            np.float32)
    return cfg, params, frames


def serve_sequential(cfg, params, prompts, *, prefix_cache, frames=None,
                     max_new=3, max_ticks=80, pool_kw=None):
    """Sequential requests through a 1P:1D frontend.

    Returns (generated sequences, frontend) — the prefill node under
    test is ``frontend.groups["default"].prefills[0]``.
    """
    kw = dict(pool_kw or POOL_KW)
    fe = ClusterFrontend(cfg, topology={"default": (1, 1)}, params=params,
                         prefix_cache=prefix_cache,
                         prefill_kwargs=dict(kw), decode_kwargs=dict(kw))
    gens = []
    for i, toks in enumerate(prompts):
        req = ServeRequest(rid=i, tokens=list(toks), max_new_tokens=max_new,
                           frames=frames)
        fe.run([req], max_ticks=max_ticks)
        assert req.done
        gens.append(list(req.generated))
    return gens, fe


def prefill_node(fe, group="default"):
    return fe.groups[group].prefills[0]


def assert_state_equal(a, b, ctx=""):
    """Bitwise equality of two mamba_state / snapshot trees
    ({(blk, sub): {leaf: array}}) — the recurrent-state parity bar."""
    assert set(a) == set(b), (ctx, set(a) ^ set(b))
    for key in sorted(a):
        assert set(a[key]) == set(b[key]), (ctx, key)
        for leaf in a[key]:
            x, y = np.asarray(a[key][leaf]), np.asarray(b[key][leaf])
            assert x.dtype == y.dtype and x.shape == y.shape, \
                (ctx, key, leaf, x.dtype, y.dtype, x.shape, y.shape)
            assert np.array_equal(x, y), \
                (ctx, key, leaf, float(np.abs(x - y).max()))


# Bucketed and exact-length prefill run the same math at different
# padded shapes. XLA picks vectorization and reduction order per shape,
# so the two agree to float32 rounding, not bitwise: a few ulps of the
# O(1) activations of the reduced configs, compounded over their layers
# and the SSD scan (at most 3.6e-7 apart on the CPU backend, every
# family). A wrong pad mask moves values by orders of magnitude more.
# Tokens must still agree exactly.
CROSS_SHAPE_TOL = dict(rtol=1e-5, atol=1e-5)


def _close(x, y, ctx):
    x, y = np.asarray(x), np.asarray(y)
    assert x.dtype == y.dtype and x.shape == y.shape, \
        (ctx, x.dtype, y.dtype, x.shape, y.shape)
    np.testing.assert_allclose(x, y, err_msg=str(ctx), **CROSS_SHAPE_TOL)


def outputs_close(a, b):
    """PrefillOutput comparison across padded shapes: first token and
    prompt length exactly; KV, recurrent state and cross-attention
    caches to ``CROSS_SHAPE_TOL``."""
    assert a.first_token == b.first_token
    assert a.prompt_len == b.prompt_len
    if a.k is not None:
        _close(a.k, b.k, "k")
        _close(a.v, b.v, "v")
    sa, sb = a.mamba_state or {}, b.mamba_state or {}
    assert set(sa) == set(sb), set(sa) ^ set(sb)
    for key in sorted(sa):
        assert set(sa[key]) == set(sb[key]), key
        for leaf in sa[key]:
            _close(sa[key][leaf], sb[key][leaf], (key, leaf))
    assert set(a.cross or {}) == set(b.cross or {})
    for key in (a.cross or {}):
        _close(a.cross[key][0], b.cross[key][0], (key, "xk"))
        _close(a.cross[key][1], b.cross[key][1], (key, "xv"))


def decode_setup(arch, n_prompts=3, seed=5):
    """(cfg, params, prompts, frames) for the decode-path suites."""
    cfg, params = reduced_params(arch)
    rng = np.random.default_rng(seed)
    prompts = [list(rng.integers(0, cfg.vocab_size, int(n)))
               for n in rng.integers(5, 14, n_prompts)]
    frames = None
    if cfg.is_encoder_decoder:
        frames = [np.asarray(
            rng.normal(size=(cfg.encoder_seq, cfg.d_model)) * 0.1,
            np.float32) for _ in prompts]
    return cfg, params, prompts, frames


def admit(pool, de, rid, out, room=10, bs=BS):
    """Alloc + write + admit one prefill output into a DecodeEngine."""
    pool.alloc(rid, out.prompt_len + room)
    if out.k is not None:
        pool.write_prefill(
            pool.owned(rid)[: (out.prompt_len + bs - 1) // bs],
            out.k, out.v)
    return de.admit(rid, out, pool.owned(rid))
