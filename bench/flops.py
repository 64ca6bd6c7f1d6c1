"""Operations and bytes that a model's work needs, from its shapes.

The counts are the family's (``bench/families/<family>.py``), looked up
from the configuration; the roofline's bound is here. A multiply-add
counts as two operations, and only what the algorithm needs is counted:
padding, masked positions and rows of empty slots are not work.
"""
from __future__ import annotations

from typing import Dict, Iterable

from bench import families


def prefill_flops(c: Dict, lens: Iterable[int]) -> float:
    """Prompts of these lengths, each run once."""
    return families.of(c).prefill_flops(c, lens)


def decode_flops(c: Dict, ctx: Iterable[int]) -> float:
    """Decoded tokens that attend over these context lengths."""
    return families.of(c).decode_flops(c, ctx)


def paged_attention_need(c: Dict, ctx: Iterable[int],
                         itemsize: int = 4) -> Dict[str, float]:
    """Least work (``flops``, ``bytes``) of the decode attention kernel
    for these live contexts, over all layers."""
    return families.of(c).paged_attention_need(c, ctx, itemsize)


def least_seconds(flops: float, bytes_: float, peaks: Dict) -> float:
    """The roofline's bound: the larger of the compute and memory times."""
    return max(flops / peaks["flops_per_s"], bytes_ / peaks["hbm_bytes_per_s"])
