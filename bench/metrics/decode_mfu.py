"""Model step: operations the decoded tokens needed (weights and head for
each token, attention over its live context) over the fused decode
step's device time times the chip's peak, in percent."""
from bench import flops
from bench.metrics_common import DECODE_MODULES, module_time


def read(ctx):
    s, n = module_time(ctx, DECODE_MODULES)
    toks = ctx["work"]["decode_ctx"]
    if not n or not toks:
        return None
    need = flops.decode_flops(ctx["config"], toks)
    return 100.0 * need / (s * ctx["peaks"]["flops_per_s"])
