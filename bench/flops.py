"""Operations and bytes that a dense decoder's work needs, from its shapes.

A multiply-add counts as two operations. Only what the algorithm needs
is counted: matrix products with the weights, attention over the tokens
that are really there (causal in prefill), and the bytes of weights and
of live keys and values read. Padding, masked positions and rows of
empty slots are not work and are not counted.
"""
from __future__ import annotations

from typing import Dict, Iterable

from bench.weights import dims


def matmul_params(c: Dict, head: bool = True) -> int:
    """Weights a token is multiplied by: every layer's projections and
    MLP, and the output head if ``head``."""
    m = dims(c)
    d, q, kv = m["d"], m["nh"] * m["hd"], m["nkv"] * m["hd"]
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * m["ff"]
    return m["layers"] * per_layer + (d * m["vocab"] if head else 0)


def weight_bytes(c: Dict, itemsize: int = 4) -> int:
    """Bytes of every weight the decode step reads (embedding rows for a
    batch are negligible and not counted)."""
    return matmul_params(c) * itemsize


def kv_bytes_per_token(c: Dict, itemsize: int = 4) -> int:
    """Key and value bytes one token holds, over all layers."""
    m = dims(c)
    return m["layers"] * 2 * m["nkv"] * m["hd"] * itemsize


def prefill_flops(c: Dict, lens: Iterable[int]) -> float:
    """Prompts of these lengths, each run once: every token through the
    layers, causal attention, the head for the last token only."""
    m = dims(c)
    body = matmul_params(c, head=False)
    head = m["d"] * m["vocab"]
    total = 0.0
    for n in lens:
        attn = 2 * m["nh"] * m["hd"] * n * (n + 1) * m["layers"]
        total += 2.0 * body * n + attn + 2.0 * head
    return total


def decode_flops(c: Dict, ctx: Iterable[int]) -> float:
    """Decoded tokens that attend over these context lengths: each token
    through every weight and the head, plus attention over its context."""
    m = dims(c)
    per_tok = 2.0 * matmul_params(c)
    attn = 4.0 * m["nh"] * m["hd"] * m["layers"]
    return sum(per_tok + attn * n for n in ctx)


def paged_attention_need(c: Dict, ctx: Iterable[int],
                         itemsize: int = 4) -> Dict[str, float]:
    """Least work of the decode attention kernel for these live contexts,
    over all layers: the keys and values of live tokens read once, the
    query read and the output written, and 4 * heads * head_dim
    operations per key (scores and weighted values)."""
    m = dims(c)
    kv = 2 * m["nkv"] * m["hd"] * itemsize
    qo = 2 * m["nh"] * m["hd"] * itemsize
    flops = bytes_ = 0.0
    for n in ctx:
        flops += 4.0 * m["nh"] * m["hd"] * n * m["layers"]
        bytes_ += (kv * n + qo) * m["layers"]
    return {"flops": flops, "bytes": bytes_}


def least_seconds(flops: float, bytes_: float, peaks: Dict) -> float:
    """The roofline's bound: the larger of the compute and memory times."""
    return max(flops / peaks["flops_per_s"], bytes_ / peaks["hbm_bytes_per_s"])
