"""Serve granite-3-8b at its published widths on one TPU chip and check it.

    python3 chip_smoke.py

One process drives the normal serving path once: ``repro.launch.serve``
builds a ``ClusterFrontend`` with 2 prefill and 2 decode nodes and the
overlapped KV transfer, and serves 4 seeded requests of 16 new tokens
each. The config keeps every width of granite-3-8b (d_model 4096, 32/8
heads, head_dim 128, d_ff 12800, vocab 49155) and cuts depth from 40 to
8 layers so that float32 weights, four KV pools and the step temporaries
fit one v5e chip's 16 GB. Weights are random, made from ``--seed``.

The run passes when every request completes, every token is in the
vocabulary, nothing non-finite appears, and every served token matches a
plain float32 reference (see ``check_streams``). Earlier lines report the
device, the cut, the agreement, whether the fused decode step carries the
Pallas paged-attention kernel (``tpu_custom_call``), the set-up and wall
seconds of this one smoke run, and the peak device bytes. Those seconds
are observations of one run, not benchmark metrics. The last line is one
JSON object: ``{"ok": ..., "device": {"platform", "kind", "count"}}``.

There is no CPU path: without a TPU, or without the repository's
``src/`` beside it, the script exits non-zero and prints no result.
``check_streams`` and ``smoke`` also run on the CPU at the reduced
config, which is how the tests rehearse this script.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.serve import serve, sized_config  # noqa: E402
from repro.models.caches import decode_slot_state  # noqa: E402
from repro.models.modeling import decode_step_jit  # noqa: E402
from repro.models.params import init_params, param_count_actual  # noqa: E402

ARCH = "granite-3-8b"
LAYERS = 8

# A served token may differ from the reference's top-1 only where the
# reference ranks it within TOL of its top logit. Why a tolerance at all:
# the served path runs float32 matmuls at TPU default precision, which
# rounds operands to bfloat16 (relative error ~2^-9), while the reference
# runs at "highest". With random weights (std 0.02) the logits have a
# scale of ~1.3 (rmsnorm'd 4096-wide hidden state times 0.02), and the
# rounding moves them by about 1% of that after 8 layers, a few
# hundredths (at most 0.057 on a TPU v5e with seed 0). A real defect (a
# wrong page, a stale KV row, a bad mask) moves a logit by the scale
# itself, far past 0.1.
TOL = 0.1


def reference_logits(cfg, params, tokens):
    """Plain causal forward of a dense decoder: (b, s) int32 tokens ->
    (b, s, vocab) float32 logits. Full attention over the whole
    sequence, no paged pool, no Pallas, no cache: the repository's own
    layers are not used, only its parameter tree."""
    b, s = tokens.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    half = hd // 2
    inv_freq = cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def norm(x, w):
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + cfg.norm_eps) * w

    def rotate(x):                              # (b, s, heads, hd)
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1)

    def layer(h, p):
        x = norm(h, p["norm"])
        q, k, v = x @ p["wq"], x @ p["wk"], x @ p["wv"]
        if "bq" in p:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        q = rotate(q.reshape(b, s, nh, hd))
        k = rotate(k.reshape(b, s, nkv, hd))
        v = v.reshape(b, s, nkv, hd)
        k = jnp.repeat(k, nh // nkv, axis=2)
        v = jnp.repeat(v, nh // nkv, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        sc = jnp.where(causal, sc, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v)
        h = h + o.reshape(b, s, nh * hd) @ p["wo"]
        x = norm(h, p["norm2"])
        m = p["mlp"]
        h = h + (jax.nn.silu(x @ m["w_gate"]) * (x @ m["w_up"])) @ m["w_down"]
        return h, None

    h = params["embed"][tokens].astype(jnp.float32)
    h, _ = jax.lax.scan(layer, h, params["blocks"]["sub0"])
    h = norm(h, params["final_norm"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return h @ head


def check_streams(cfg, params, requests) -> dict:
    """Hold each served stream to the plain reference, step by step.

    The reference runs the prompt plus the served tokens once under
    ``jax.default_matmul_precision("highest")``; a causal forward's
    logits at position t do not depend on later tokens, so row t is what
    a per-step full forward over that prefix gives. At every step the
    served token must be the reference's top-1, or lie within ``TOL`` of
    it (a near-tie the default-precision path may break the other way).
    Up to the first step whose top-1/top-2 gap is under ``TOL`` this is
    exact agreement with the reference's greedy stream; past it the
    check goes on against the served context."""
    lens = [len(r.tokens) + len(r.generated) - 1 for r in requests]
    width = -(-max(lens) // 8) * 8
    toks = np.zeros((len(requests), width), np.int32)
    for i, r in enumerate(requests):
        seq = list(r.tokens) + list(r.generated[:-1])
        toks[i, :len(seq)] = seq
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda p, t: reference_logits(cfg, p, t))
        logits = np.asarray(fwd(params, jnp.asarray(toks)))
    finite = bool(np.isfinite(logits).all())
    steps = agree = near_ties = 0
    worst = 0.0                   # largest top-1 minus served-token logit
    failures = []
    for i, r in enumerate(requests):
        for t, tok in enumerate(r.generated):
            row = logits[i, len(r.tokens) - 1 + t]
            top2 = np.partition(row, -2)[-2:]
            gap = float(top2[1] - top2[0])
            short = float(row.max() - row[tok])
            steps += 1
            agree += int(tok == int(row.argmax()))
            near_ties += int(gap < TOL)
            worst = max(worst, short)
            if short >= TOL:
                failures.append((r.rid, t, tok, int(row.argmax()), short))
    return {"finite": finite, "steps": steps, "agree": agree,
            "near_ties": near_ties, "worst_shortfall": worst,
            "failures": failures, "ok": finite and not failures}


def fused_step_hlo(cfg, params, node) -> str:
    """Lowered text of the fused decode step at a decode node's shapes."""
    n = node.engine.max_slots
    sds = jax.ShapeDtypeStruct
    vec = sds((n,), jnp.int32)
    slots = jax.eval_shape(lambda: decode_slot_state(cfg, n))
    storage = sds(node.pool.storage.shape, node.pool.storage.dtype)
    return decode_step_jit.lower(
        cfg, params, storage, sds((n, 4), jnp.int32), vec, vec,
        sds((n,), jnp.bool_), slots,
        block_size=node.pool.block_size).as_text()


def smoke(cfg, params, *, prefills: int = 2, decodes: int = 2,
          requests: int = 4, max_new_tokens: int = 16,
          seed: int = 0) -> dict:
    """Serve the same seeded requests twice through ``launch.serve`` (the
    first pass compiles every shape, the second runs compiled) and check
    the second. Returns the report; raises if a phase fails."""
    log = partial(print, flush=True)
    kw = dict(params=params, requests=requests, prefills=prefills,
              decodes=decodes, max_new_tokens=max_new_tokens, seed=seed)
    t0 = time.perf_counter()
    first = [list(r.generated) for r in serve(cfg, **kw).requests]
    setup_s = time.perf_counter() - t0
    run = serve(cfg, **kw)
    reqs = run.requests
    group = run.frontend.groups["default"]
    nodes = list(group.prefills) + list(group.decodes)
    done = sum(r.done for r in reqs)
    n_tok = sum(len(r.generated) for r in reqs)
    in_vocab = all(0 <= t < cfg.vocab_size for r in reqs for t in r.generated)
    pools_finite = all(bool(jnp.isfinite(n.pool.storage).all())
                       for n in nodes)
    repeat = [list(r.generated) for r in reqs] == first
    ref = check_streams(cfg, params, reqs)
    kernel = "tpu_custom_call" in fused_step_hlo(cfg, params,
                                                 group.decodes[0])
    log(f"served: {done}/{len(reqs)} requests, {n_tok} tokens through "
        f"ClusterFrontend {prefills}P/{decodes}D, transfers="
        f"{int(run.transfer_stats['jobs_admitted'])} "
        f"({'overlapped' if run.transfer_stats['overlapped'] else 'blocking'})")
    log(f"tokens in vocabulary: {in_vocab}; KV pools finite: "
        f"{pools_finite}; reference logits finite: {ref['finite']}; "
        f"second pass repeats the first: {repeat}")
    log(f"reference agreement: {ref['agree']}/{ref['steps']} tokens are "
        f"the reference top-1 ({ref['agree'] / ref['steps']:.4f}); "
        f"{ref['near_ties']} steps had a top-1/top-2 gap < {TOL}; largest "
        f"served-token shortfall {ref['worst_shortfall']:.6f} (limit {TOL})")
    for rid, t, tok, top, short in ref["failures"][:8]:
        log(f"  MISMATCH rid={rid} step={t}: served {tok}, reference "
            f"top-1 {top}, shortfall {short:.6f}")
    log(f"fused decode step contains tpu_custom_call: {kernel}")
    log(f"set-up of this smoke run (first pass, compilation included): "
        f"{setup_s:.3f} s; wall time of the compiled second pass: "
        f"{run.wall_s:.3f} s")
    for r in reqs:
        log(f"  rid={r.rid} prompt[{len(r.tokens)}] -> {r.generated}")
    ok = (done == len(reqs) and in_vocab and pools_finite and repeat
          and ref["ok"])
    return {"ok": ok, "pallas_in_decode_step": kernel, "reference": ref,
            "setup_s": setup_s, "wall_s": run.wall_s}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args(argv)
    cache_dir = use_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform}); this script has no CPU path",
              file=sys.stderr)
        return 1
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}; compile cache: {cache_dir}", flush=True)

    cfg, cuts = sized_config(ARCH, layers=LAYERS)
    for cut in cuts:
        print(f"reduced: {cut}")
    print(f"config: {cfg.name} d_model={cfg.d_model} heads={cfg.num_heads}"
          f"/{cfg.num_kv_heads} head_dim={cfg.hd} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} layers={cfg.num_layers} float32, "
          f"{param_count_actual(cfg)} params", flush=True)
    params = init_params(cfg, jax.random.PRNGKey(a.seed))
    rep = smoke(cfg, params, seed=a.seed)
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')} of "
          f"bytes_limit {stats.get('bytes_limit')}")
    ok = bool(rep["ok"] and rep["pallas_in_decode_step"])
    print(json.dumps({"ok": ok, "device": {"platform": dev.platform,
                                           "kind": dev.device_kind,
                                           "count": len(devices)}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
