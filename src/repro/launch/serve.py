"""Serving launcher: run the real-compute P/D cluster (ClusterFrontend over
prefill and decode nodes) with a batched synthetic workload.

Without ``--reduced`` the published config is served at full width;
``--layers N`` cuts depth only. ``--reduced`` is the CPU toy cut.

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-8b \
      --reduced --requests 16 --prefills 2 --decodes 2
"""
from __future__ import annotations

import argparse
import sys
import time
from typing import Dict, List, NamedTuple, Tuple

import numpy as np

from repro.configs import ALIASES, get_config
from repro.launch.compile_cache import use_compile_cache
from repro.models.config import ModelConfig
from repro.serving.cluster import ServeRequest
from repro.serving.frontend import ClusterFrontend


class Served(NamedTuple):
    requests: List[ServeRequest]
    transfer_stats: Dict[str, float]
    frontend: ClusterFrontend
    wall_s: float


def sized_config(arch: str, *, reduced: bool = False,
                 layers: int = 0) -> Tuple[ModelConfig, List[str]]:
    """The served config and the list of cuts applied to it."""
    cfg = get_config(arch)
    cuts: List[str] = []
    if reduced:
        cfg = cfg.reduced()
        cuts.append("widths and depth by ModelConfig.reduced() (CPU toy)")
    if layers and layers != cfg.num_layers:
        cuts.append(f"num_layers {cfg.num_layers}->{layers}")
        cfg = cfg.replace(num_layers=layers)
    return cfg, cuts


def make_requests(cfg: ModelConfig, n: int, max_new_tokens: int,
                  seed: int) -> List[ServeRequest]:
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        ln = int(rng.integers(6, 20))
        frames = None
        if cfg.is_encoder_decoder:   # stub audio frontend embeddings
            frames = rng.normal(size=(cfg.encoder_seq, cfg.d_model)) * 0.1
        reqs.append(ServeRequest(
            rid=i, tokens=list(map(int, rng.integers(0, cfg.vocab_size, ln))),
            max_new_tokens=max_new_tokens, frames=frames))
    return reqs


def serve(cfg: ModelConfig, *, params=None, requests: int = 12,
          prefills: int = 2, decodes: int = 2, max_new_tokens: int = 8,
          transfer: str = "block_free", overlap: bool = True,
          seed: int = 0) -> Served:
    """Serve ``requests`` seeded synthetic requests through one P/D group
    and return them with the group's transfer ledger."""
    fe = ClusterFrontend(cfg, topology={"default": (prefills, decodes)},
                         seed=seed, transfer_mode=transfer, params=params,
                         flat_iids=True, overlap_transfer=overlap)
    reqs = make_requests(cfg, requests, max_new_tokens, seed)
    t0 = time.perf_counter()
    done = fe.run(reqs)
    wall = time.perf_counter() - t0
    return Served(done, fe.groups["default"].transfer_stats(), fe, wall)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b", choices=sorted(ALIASES))
    ap.add_argument("--reduced", action="store_true",
                    help="serve the reduced same-family variant (CPU)")
    ap.add_argument("--layers", type=int, default=0,
                    help="depth cut that keeps every width")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--prefills", type=int, default=2)
    ap.add_argument("--decodes", type=int, default=2)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--transfer", default="block_free",
                    choices=["block_free", "block_fixed"])
    ap.add_argument("--no-overlap", action="store_true",
                    help="blocking in-tick transfer instead of the "
                         "overlapped layer-wise pipeline")
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args(argv)

    use_compile_cache()
    cfg, cuts = sized_config(a.arch, reduced=a.reduced, layers=a.layers)
    for cut in cuts:
        print(f"reduced: {cut}")
    print(f"[serve] {cfg.name}: {a.prefills}P/{a.decodes}D "
          f"transfer={a.transfer}")
    run = serve(cfg, requests=a.requests, prefills=a.prefills,
                decodes=a.decodes, max_new_tokens=a.max_new_tokens,
                transfer=a.transfer, overlap=not a.no_overlap, seed=a.seed)
    done, tf = run.requests, run.transfer_stats
    ok = sum(r.done for r in done)
    path = "overlapped pipeline" if tf["overlapped"] else "blocking"
    print(f"[serve] {ok}/{len(done)} completed in {run.wall_s:.1f}s wall; "
          f"gateway rejections={run.frontend.rejections}; "
          f"transfers={int(tf['jobs_admitted'])} ({path}) "
          f"mean_admission_wait={tf['admission_wait_mean_s']*1e3:.2f}ms")
    for r in done[:4]:
        print(f"  rid={r.rid} prompt[{len(r.tokens)}] -> {r.generated}")
    return 0 if ok == len(done) else 1


if __name__ == "__main__":
    sys.exit(main())
