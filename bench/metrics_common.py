"""Names the per-layer metrics look for in a reduced trace, and helpers.

Programs are matched by their module name in the device trace (a jitted
function ``f`` runs as ``jit_f``), operations by ``"<program>|<op>
<opcode>"`` (a tuple pattern needs all of its parts). The transfer path
runs eagerly: its Pallas scatter dispatches as ``jit_wrapped``, the layer
slice and the write back into the pool as ``jit_slice`` and
``jit_dynamic_update_slice``; the paged attention kernel is the one
Pallas kernel (``tpu_custom_call``) inside the fused decode step.
"""
from __future__ import annotations

from typing import Iterable, Tuple

from bench import trace_reduce

DECODE_MODULES = ("jit_forward_decode_step",)
PREFILL_MODULES = ("jit_forward_prefill",)
KV_MOVE_MODULES = ("jit_wrapped", "jit_slice", "jit_dynamic_update_slice")
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
