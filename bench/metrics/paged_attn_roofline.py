"""Kernels: the paged decode attention kernel's least time for the work
it needed (keys and values of live tokens only, query and output, scores
and weighted values) over its device time, in percent."""
from bench import flops
from bench.metrics_common import PAGED_ATTN_OPS, op_time


def read(ctx):
    s, n = op_time(ctx, PAGED_ATTN_OPS)
    toks = ctx["work"]["decode_ctx"]
    if not n or not toks:
        return None
    need = flops.paged_attention_need(ctx["config"], toks)
    least = flops.least_seconds(need["flops"], need["bytes"], ctx["peaks"])
    return 100.0 * least / s
