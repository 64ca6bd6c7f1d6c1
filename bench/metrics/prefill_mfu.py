"""Prefill lane: operations the real prompt tokens needed (no padding,
causal attention) over the prefill program's device time times the
chip's peak, in percent."""
from bench import flops
from bench.metrics_common import PREFILL_MODULES, module_time


def read(ctx):
    s, n = module_time(ctx, PREFILL_MODULES)
    lens = ctx["work"]["prefill_lens"]
    if not n or not lens:
        return None
    need = flops.prefill_flops(ctx["config"], lens)
    return 100.0 * need / (s * ctx["peaks"]["flops_per_s"])
