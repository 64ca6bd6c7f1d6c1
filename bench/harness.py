"""One benchmark run of one cell: build, warm up, measure, check.

``run.py`` is the command; this module holds the steps so that the tests
can drive them on the CPU at a reduced size. A cell is an entry of
``BENCHMARK.json``'s ``workloads``; everything else is found by name:

- ``bench/configs/<config>.json``: the model's sizes, its precision and
  how it is served (topology, pool and slot sizes);
- ``bench/traffic/<mix>.json``: the traffic, read by
  ``bench/traffic/gen_<generator>.py``;
- ``bench/metrics/<metric>.py``: one reader per per-layer metric.

The run drives the real serving path, ``ClusterFrontend.submit`` and
``ClusterFrontend.serve``, one event at a time, and stamps every token
with the host's clock as it reaches the client. No number is read from
the program's virtual-time ledgers.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402

from bench import reference, weights  # noqa: E402

# Correctness: at every served position of every finished request, the
# gap by which the served token's logit lies below the best logit of the
# plain float32 reference, run at the configuration's matmul precision.
# The number compared is the share of served tokens whose gap passes the
# configuration's ``near_tie_logit``: the program and the reference sum
# in different orders and round different activations to bfloat16, so
# near-ties may break the other way, and the widest gap swings with the
# closest tie a run meets (it does not separate the bfloat16 control);
# a token that loses by more than a near-tie is rare in the program and
# common in the control. The share may not pass the configuration's
# ``past_near_tie_limit_pct``. Each limit and the readings it was set
# from are in PERF.md, section 2.
# Served tokens the check reads at least.
MIN_CHECKED = 300
# Seconds past the window in which every request due in it has to finish.
DRAIN_S = 90.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# ---------------------------------------------------------------- loading
@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    mix: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moves = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"]
           if (name in m["workloads"] if "workloads" in m
               else m["moves"] in moves)]
    return Cell(name, int(w["chips"]), load_json(root / cfg["file"]),
                load_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
                e2e, per)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generate(cell: Cell, seed: int, seconds: float) -> Dict:
    gen = load_module(BENCH / "traffic"
                      / f"gen_{cell.mix['generator']}.py")
    return gen.generate(cell.mix, seed=seed, seconds=seconds,
                        vocab=cell.config["vocab_size"])


def program_config(c: Dict):
    """The serving program's config for a configuration file."""
    from repro.models.config import ModelConfig
    m = weights.dims(c)
    return ModelConfig(
        name=c["name"], arch_type="dense", num_layers=m["layers"],
        d_model=m["d"], num_heads=m["nh"], num_kv_heads=m["nkv"],
        d_ff=m["ff"], vocab_size=m["vocab"], head_dim=m["hd"],
        qkv_bias=bool(c.get("attention_bias", False)),
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
        tie_embeddings=m["tied"],
        max_seq_len=int(c["max_position_embeddings"]))


def check_weights(c: Dict, params) -> None:
    """The benchmark's weight tree has the program's layout."""
    from repro.models.params import abstract_params
    want = jax.tree.map(lambda s: s.shape, abstract_params(program_config(c)))
    got = jax.tree.map(lambda a: a.shape, params)
    if want != got:
        raise SystemExit(f"weight layout differs from the program's: "
                         f"{got} vs {want}")


# ------------------------------------------------------------------ sizes
def blocks(tokens: int, bs: int) -> int:
    return -(-tokens // bs)


def pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def prefill_bucket(n: int) -> int:
    """The serving program's prefill length bucket (powers of two >= 16)."""
    return max(16, pow2(n))


def check_sizes(serve: Dict, traffic: Dict) -> None:
    """Pools large enough for the worst case the traffic can reach: a
    prefill node holds at most 2 * batch - 1 prompts (a forming batch
    beside a waiting one), a decode pool at most ``max_slots`` full
    allocations (prompt + output + 1). The program raises if a pool runs
    out, so a mix that could overrun one is refused here."""
    p, d = serve["prefill"], serve["decode"]
    plens = [len(x) for x in traffic["prompts"]]
    totals = [n + o + 1 for n, o in zip(plens, traffic["outputs"])]
    need_p = (2 * p["batch_size"] - 1) * blocks(max(plens), p["block_size"])
    need_d = d["max_slots"] * blocks(max(totals), d["block_size"])
    if p["num_blocks"] < need_p or d["num_blocks"] < need_d:
        raise SystemExit(
            f"pools too small for this traffic: prefill {p['num_blocks']} "
            f"blocks (need {need_p}), decode {d['num_blocks']} (need {need_d})")


def warm_plan(serve: Dict, traffic: Dict) -> List[List[tuple]]:
    """Waves of (prompt, output) sizes that compile and run every shape the
    window can use, each wave submitted at once so that it forms one
    prefill batch. The first wave is a longest prompt alone, with an
    output just long enough to widen the decode block table to the width
    the traffic reaches, so the decode step compiles for that width only.
    Then, per prefill bucket and per batch size up to the node's, waves
    that put every prompt length of the bucket into a batch of that size
    once: the program compiles small programs per prompt length and
    batch size, besides the prefill per bucket and batch size."""
    bs = serve["decode"]["block_size"]
    batch = serve["prefill"]["batch_size"]
    plens = [len(x) for x in traffic["prompts"]]
    outs = traffic["outputs"]
    width = max(pow2(blocks(n + o + 1, bs)) for n, o in zip(plens, outs))
    longest = max(plens)
    first = next(o for o in range(1, max(outs) + 1)
                 if pow2(blocks(longest + o + 1, bs)) == width)
    waves = [[(longest, first)]]
    by_bucket: Dict[int, List[int]] = {}
    for n in sorted(set(plens)):
        by_bucket.setdefault(prefill_bucket(n), []).append(n)
    for lens in by_bucket.values():
        for b in range(batch, 0, -1):
            for k in range(0, len(lens), b):
                waves.append([(lens[(k + i) % len(lens)], 1)
                              for i in range(b)])
    return waves


# ---------------------------------------------------------------- serving
def build(cell: Cell, params, seed: int):
    from repro.serving.frontend import ClusterFrontend
    s = cell.config["serve"]
    n_p, n_d = s["topology"]
    return ClusterFrontend(
        program_config(cell.config), topology={"default": (n_p, n_d)},
        params=params, seed=seed, flat_iids=True, overlap_transfer=True,
        prefill_kwargs=dict(s["prefill"]), decode_kwargs=dict(s["decode"]))


def has_work(fe) -> bool:
    if fe.arrivals:
        return True
    return any(g.next_time() is not None for g in fe.groups.values())


def next_kind(fe) -> str:
    """What the next ``serve`` event is, for the host span's name."""
    t_arr = fe.arrivals[0][0] if fe.arrivals else None
    best, kind = None, "idle"
    for g in fe.groups.values():
        t = g.next_time()
        if t is not None and (best is None or t < best):
            best = t
            kind = g.events[0][2] if g.events and g.events[0][0] <= t \
                else "segment"
    if t_arr is not None and (best is None or t_arr <= best):
        kind = "arrival"
    return kind


def serve_waves(fe, cfg_vocab: int, waves: List[List[tuple]],
                rng: np.random.Generator, firsts: List[int]) -> None:
    """Serve each wave to completion. Warm-up prompts start with tokens
    that no prompt of the window starts with, so they leave nothing in
    the prefix cache that the window could hit."""
    from repro.serving.cluster import ServeRequest
    rid = -1
    for wave in waves:
        reqs = []
        for plen, olen in wave:
            first = firsts[(-rid - 1) % len(firsts)]
            reqs.append(ServeRequest(
                rid=rid, tokens=[first] + rng.integers(
                    0, cfg_vocab, plen - 1).tolist(),
                max_new_tokens=olen))
            rid -= 1
        for r in reqs:
            fe.submit(r, at=fe.now)
        fe.serve(watch=reqs)
        if not all(r.done and not r.shed for r in reqs):
            raise RuntimeError("a warm-up request did not finish")


def counters(fe) -> Dict[str, float]:
    """The program's own counts, read before and after the window."""
    from repro.serving.engine import prefill_compile_count
    g = fe.groups["default"]
    pe = [p.engine for p in g.prefills]
    de = [d.engine for d in g.decodes]
    pools = [p.pool for p in g.prefills]
    return {
        "rejections": float(fe.rejections),
        "gw_requeues": float(fe.gw_requeues),
        "gw_sheds": float(fe.gw_sheds),
        "compute_tokens": float(sum(e.compute_tokens for e in pe)),
        "padded_tokens": float(sum(e.padded_tokens for e in pe)),
        "prefill_batches": float(sum(e.prefill_batches for e in pe)),
        "prefill_compiles": float(prefill_compile_count()),
        "decode_steps": float(sum(e.fused_steps for e in de)),
        "max_slots": float(sum(e.max_slots for e in de)),
        "handed_to_decode": float(g.sched.n_admitted if g.sched else 0),
        "prefix_lookups": float(sum(p.lookups for p in pools)),
        "prefix_hits": float(sum(p.hits for p in pools)),
        "prefix_hit_tokens": float(sum(p.hit_tokens for p in pools)),
    }


@dataclass
class Track:
    """One request as the client sees it."""
    req: object
    plen: int
    stamps: List[float] = field(default_factory=list)


class CompileCounter:
    """Counts programs built (compiled or loaded from the cache)."""

    def __init__(self):
        self.seen: List[tuple] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.seen.append((time.perf_counter(), kw.get("fun_name", "?")))

    def between(self, a: float, b: float) -> List[str]:
        return [name for t, name in self.seen if a <= t <= b]


def drive(fe, traffic: Dict, t0: float, seconds: float,
          annotate=None, on_end=None) -> Dict:
    """Submit each request when it falls due on the host's clock, advance
    the frontend one event at a time, stamp tokens as they appear; then
    serve every request due in the window to completion (at most
    ``DRAIN_S`` more seconds)."""
    from repro.serving.cluster import ServeRequest
    end = t0 + seconds
    tracks: List[Track] = []
    live: List[Track] = []
    late: List[float] = []
    busy = 0.0
    i, n = 0, len(traffic["due_s"])
    due = [t0 + d for d in traffic["due_s"]]
    drain_end = end + DRAIN_S
    span = annotate or (lambda name: contextlib.nullcontext())
    while True:
        now = time.perf_counter()
        if on_end is not None and now >= end:
            on_end()
            on_end = None
        while i < n and due[i] <= now:
            r = ServeRequest(rid=i, tokens=traffic["prompts"][i],
                             max_new_tokens=traffic["outputs"][i])
            with span("bench.submit"):
                fe.submit(r, at=fe.now)
            tr = Track(r, len(r.tokens))
            tracks.append(tr)
            live.append(tr)
            late.append(now - due[i])
            i += 1
        if now >= end and (not live or now >= drain_end):
            break
        if has_work(fe):
            a = time.perf_counter()
            with span("bench.serve." + next_kind(fe)):
                fe.serve(max_events=1)
            t = time.perf_counter()
            busy += t - a
            still = []
            for tr in live:
                g = len(tr.req.generated)
                if g > len(tr.stamps):
                    tr.stamps.extend([t] * (g - len(tr.stamps)))
                if not tr.req.done:
                    still.append(tr)
            live = still
        else:
            wake = min(due[i] if i < n else drain_end,
                       end if now < end else drain_end)
            with span("bench.sleep"):
                time.sleep(max(0.0, wake - now))
    if on_end is not None:
        on_end()
    return {"tracks": tracks, "late": late, "host_busy_s": busy,
            "submitted": i, "end": end}


# ----------------------------------------------------------------- metrics
def pct(xs: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))


def end_to_end(run: Dict, t0: float, seconds: float) -> Dict[str, float]:
    """Tokens per second over the window, and the 95th percentile of
    every gap between successive tokens that ends in it."""
    end = t0 + seconds
    gaps = []
    tokens = 0
    for tr in run["tracks"]:
        st = tr.stamps
        tokens += sum(1 for s in st if s <= end)
        gaps += [(b - a) * 1e3 for a, b in zip(st, st[1:]) if b <= end]
    out = {"output_tok_s": tokens / seconds}
    if gaps:
        out["tpot_p95_ms"] = pct(gaps, 95)
    return out


def window_work(run: Dict, a: float, b: float) -> Dict:
    """What the program did for clients between host times a and b: prompt
    lengths of first tokens, and the context length of every decoded
    token (the step attends over the prompt and every token before)."""
    prefills, ctx = [], []
    for tr in run["tracks"]:
        for j, s in enumerate(tr.stamps):
            if a <= s <= b:
                if j == 0:
                    prefills.append(tr.plen)
                else:
                    ctx.append(tr.plen + j)
    return {"prefill_lens": prefills, "decode_ctx": ctx}


# ------------------------------------------------------------- correctness
def checked_streams(run: Dict) -> List[tuple]:
    """(prompt, served tokens) of every finished request."""
    return [(tr.req.tokens, list(tr.req.generated)) for tr in run["tracks"]
            if tr.req.done and not tr.req.shed]


def past_near_tie_pct(gaps: List[np.ndarray], near_tie: float) -> float:
    """Percent of served tokens whose gap passes ``near_tie``."""
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    return 100.0 * float((g > near_tie).mean()) if g.size else math.inf


def check(cell: Cell, params, run: Dict) -> Dict[str, Dict]:
    """Numbers compared, each beside its limit."""
    c = cell.config
    streams = checked_streams(run)
    gaps = reference.served_gaps(c, params, streams)["served"]
    if gaps:
        log(f"widest gap {max(float(g.max()) for g in gaps)} over "
            f"{len(gaps)} requests")
    vocab = c["vocab_size"]
    bad_ids = sum(1 for tr in run["tracks"] for t in tr.req.generated
                  if not 0 <= t < vocab)
    unfinished = sum(1 for tr in run["tracks"]
                     if tr.req.shed or not tr.req.done)
    checked = sum(len(s) for _, s in streams)
    return {
        "past_near_tie_pct": {
            "value": past_near_tie_pct(gaps, c["near_tie_logit"]),
            "limit": c["past_near_tie_limit_pct"], "rule": "<="},
        "checked_tokens": {"value": checked, "limit": MIN_CHECKED,
                           "rule": ">="},
        "unfinished_requests": {"value": unfinished, "limit": 0,
                                "rule": "<="},
        "token_ids_out_of_vocab": {"value": bad_ids, "limit": 0,
                                   "rule": "<="},
    }


def holds(c: Dict) -> bool:
    v, lim = c["value"], c["limit"]
    if v is None or not math.isfinite(v):
        return False
    return v <= lim if c["rule"] == "<=" else v >= lim


def finite(x):
    """The result with every non-finite number as null (strict JSON)."""
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return None
    return x


# ------------------------------------------------------------------ device
def device_info(chips: int, require_tpu: bool = True) -> Dict:
    devs = jax.devices()
    d = devs[0]
    if require_tpu and (d.platform != "tpu" or len(devs) < chips):
        raise SystemExit(f"no TPU with {chips} chip(s): JAX found "
                         f"{len(devs)} {d.platform} device(s)")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs), "_devices": devs[:chips]}


def peak_bytes(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def peaks_for(kind: str) -> Dict:
    table = load_json(BENCH / "peaks.json")
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table[kind]
