"""The control: the plain reference computed in bfloat16, put in the
program's place, puts more served tokens past the near-tie than a
float32 program and fails a limit placed between the two, as the chip's
limit is placed (PERF.md, section 2).

At this test size on the CPU the float32 stand-in for the program is the
reference's own first-ranked token at every position of a context, so it
reads gap 0; the control reads the gap of the token bfloat16 ranks
first at the same positions."""
from __future__ import annotations

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness as H
from bench import reference, weights

SMALL = H.load_json(Path(__file__).resolve().parent / "data" / "small.json")


def _greedy(c, params, seed, n=3, plen=160, slen=64):
    """Prompts of random tokens, each continued greedily by the float32
    reference: a float32 program's stream, whose gaps are 0."""
    rng = np.random.default_rng(seed)
    width = reference.PAD
    fwd = jax.jit(lambda p, t: reference.logits(c, p, t)[0])
    out = []
    for _ in range(n):
        seq = rng.integers(0, c["vocab_size"], plen).tolist()
        for _ in range(slen):
            toks = np.zeros((1, width), np.int32)
            toks[0, :len(seq)] = seq
            with jax.default_matmul_precision(c["matmul_precision"]):
                row = np.asarray(fwd(params, jnp.asarray(toks))[len(seq) - 1])
            seq.append(int(row.argmax()))
        out.append((seq[:plen], seq[plen:]))
    return out


@pytest.mark.parametrize("seed", [3, 2**31 + 11, 40])
def test_control_fails_a_limit_the_program_meets(seed):
    c = reference._frozen(SMALL)
    params = weights.make(SMALL, seed)
    streams = _greedy(c, params, seed)
    g = reference.served_gaps(SMALL, params, streams, control=jnp.bfloat16)
    tie = SMALL["near_tie_logit"]
    program = H.past_near_tie_pct(g["served"], tie)
    control = H.past_near_tie_pct(g["control"], tie)
    assert control > 0.0 and control >= 3 * program, (program, control)
    limit = (program + control) / 2
    assert not H.holds({"value": control, "limit": limit, "rule": "<="})
    assert H.holds({"value": program, "limit": limit, "rule": "<="})
