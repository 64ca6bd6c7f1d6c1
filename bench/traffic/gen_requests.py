"""The one traffic generator: request sizes and arrival times from a mix file.

A mix file (``bench/traffic/<mix>.json``) names this generator with
``"generator": "requests"`` and gives:

- ``prompt`` and ``output``: a length distribution each, as
  ``{"dist": "lognormal", "median", "sigma", "min", "max", "levels"}`` or
  ``{"dist": "uniform", "min", "max", "levels"}``. The distribution is
  represented by ``levels`` quantiles, clipped to ``[min, max]``;
- ``arrivals``: ``{"process": "gamma", "rate_rps", "cv"}``, an open loop
  (Poisson is ``cv`` 1);
- ``size_seed``: fixes the pairing of prompt and output lengths, the
  gaps between arrivals, and the order of both.

Prompts share no prefix: every prompt of a run starts with a token no
other prompt starts with, and ``spare_first`` lists further unused first
tokens for the warm-up's prompts, so the prefix cache never hits.

Every ``--seed`` gets the same (prompt, output) sizes and gaps in the
same order, with token ids drawn from the seed. So a seed changes neither
how much work a run holds nor when each size arrives: with some tens of
requests to a window, a drawn order would decide which fall inside it.
"""
from __future__ import annotations

from statistics import NormalDist
from typing import Dict, List

import numpy as np

# first tokens kept back for the warm-up's prompts
SPARE = 256


def levels(spec: Dict) -> List[int]:
    """The distinct lengths that stand for a length distribution."""
    k = int(spec["levels"])
    lo, hi = int(spec["min"]), int(spec["max"])
    qs = [(i + 0.5) / k for i in range(k)]
    if spec["dist"] == "lognormal":
        nd = NormalDist()
        raw = [spec["median"] * np.exp(spec["sigma"] * nd.inv_cdf(q))
               for q in qs]
    elif spec["dist"] == "uniform":
        raw = [lo + (hi - lo) * q for q in qs]
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return [int(min(max(round(x), lo), hi)) for x in raw]


def sizes(mix: Dict, n: int) -> List[tuple]:
    """``n`` (prompt, output) pairs: every prompt level equally often,
    paired with output levels by a permutation fixed by ``size_seed``."""
    p, o = levels(mix["prompt"]), levels(mix["output"])
    perm = np.random.default_rng(mix["size_seed"]).permutation(n)
    return [(p[j % len(p)], o[int(perm[j]) % len(o)]) for j in range(n)]


def generate(mix: Dict, *, seed: int, seconds: float, vocab: int) -> Dict:
    """Requests of one run, sorted by due time.

    Returns ``{"due_s", "prompts", "outputs", "spare_first"}``: due times
    in seconds after the window opens, token-id lists, output lengths,
    and first tokens no prompt uses."""
    arr = mix["arrivals"]
    if arr["process"] != "gamma":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    n = max(1, int(round(arr["rate_rps"] * seconds)))
    shape = 1.0 / arr["cv"] ** 2
    gaps = np.random.default_rng(mix["size_seed"]).gamma(shape, 1.0, n)
    rng = np.random.default_rng(seed)
    shuffle = np.random.default_rng([mix["size_seed"], 1])
    if n + SPARE > vocab:
        raise ValueError(f"{n} unshared prompts need a vocabulary of more "
                         f"than {n + SPARE} tokens")
    firsts = rng.permutation(vocab)[:n + SPARE]
    pairs = sizes(mix, n)
    order = shuffle.permutation(n)
    g = gaps[shuffle.permutation(n)]
    due = np.concatenate([[0.0], np.cumsum(g)[:-1]]) * (seconds / g.sum())
    prompts, outputs = [], []
    for k, j in enumerate(order):
        plen, olen = pairs[int(j)]
        prompts.append([int(firsts[k])]
                       + rng.integers(0, vocab, plen - 1).tolist())
        outputs.append(olen)
    return {"due_s": due.tolist(), "prompts": prompts, "outputs": outputs,
            "spare_first": firsts[n:].tolist()}
