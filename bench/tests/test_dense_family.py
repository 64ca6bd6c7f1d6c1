"""The dense family is the benchmark's dense code moved, not changed: for
the test configurations at a fixed seed, the weight tree, the reference
logits and the bfloat16 control's tokens are bitwise those of the code
as it stood before it moved into ``bench/families/dense.py``. The
checksums were recorded by running this test's ``readings`` with that
earlier code on the CPU."""
from __future__ import annotations

import hashlib
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, weights
from bench.harness import load_json

DATA = Path(__file__).resolve().parent / "data"
SEED = 2**31 + 7

BEFORE = {
    "tiny": {
        "weights": "d81a1c5328fb11d19fc57b2267eb1da302b4a3591b7daf6614c0b39540d3fede",
        "logits": "bf9a9aa7fc9d09f7d00e2bdbd14ede2ada286bf7aae0bf47689c5d9b0fd3574b",
        "control": "940bec058eb346f074b8656b6b18ff762e7b3d26ad9532b58b7514e115b420f0",
    },
    "small": {
        "weights": "93d7923eb73754d1f54c516126b9747b7a2003fa912379573c8ff02d300db418",
        "logits": "1628d78d2f360a0889000ecc3760f4cdb3b14209541019ea162e84fc31266a70",
        "control": "4ad12ee545591bcc95b59971893f49e3dd805f6531d9a03ee70d08d8ac48c45b",
    },
}


def digest(tree) -> str:
    """SHA-256 over every leaf's path, dtype and bytes, in tree order."""
    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(x)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def readings(c) -> dict:
    params = weights.make(c, SEED)
    toks = jnp.asarray(np.random.default_rng(5).integers(
        0, c["vocab_size"], (1, 96)), jnp.int32)
    fc = reference._frozen(c)
    with jax.default_matmul_precision(c["matmul_precision"]):
        lg = jax.jit(lambda p, t: reference.logits(fc, p, t))(params, toks)
    ct = reference.control_tokens(c, params, toks, jnp.bfloat16)
    return {"weights": digest(params), "logits": digest(lg),
            "control": digest(ct)}


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_dense_family_is_bitwise_the_code_it_replaced(name):
    c = load_json(DATA / f"{name}.json")
    assert c["family"] == "dense"
    assert readings(c) == BEFORE[name]
