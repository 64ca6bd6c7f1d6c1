"""Names the per-layer metrics look for in a reduced trace, and helpers.

Programs are matched by their module name in the device trace (a jitted
function ``f`` runs as ``jit_f``), operations by ``"<program>|<op>
<opcode>"`` (a tuple pattern needs all of its parts). The KV hand-off's
per-layer scatter into the decode pool is one cached program per block
count, ``jit_kv_scatter_layer`` (``kernels/ops.kv_scatter_layer``), which
writes the received blocks of one layer into the donated pool in place;
the paged attention kernel is the one Pallas kernel (``tpu_custom_call``)
inside the fused decode step.
"""
from __future__ import annotations

from typing import Iterable, Tuple

from bench import trace_reduce

DECODE_MODULES = ("jit_forward_decode_step",)
PREFILL_MODULES = ("jit_forward_prefill",)
KV_MOVE_MODULES = ("jit_kv_scatter_layer",)
PAGED_ATTN_OPS = (("jit_forward_decode_step|", " tpu_custom_call"),)


def module_time(ctx, names: Iterable[str]) -> Tuple[float, int]:
    red = ctx.get("trace")
    if not red:
        return 0.0, 0
    return (trace_reduce.seconds_of(red["modules"], names),
            trace_reduce.count_of(red["modules"], names))


def op_time(ctx, names: Iterable[str]) -> Tuple[float, int]:
    red = ctx.get("trace")
    if not red:
        return 0.0, 0
    return (trace_reduce.seconds_of(red["ops"], names),
            trace_reduce.count_of(red["ops"], names))


def idle_share(ctx):
    red = ctx.get("trace")
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
